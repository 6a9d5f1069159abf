"""The benchmark's own tests: run by hand and in a builder's rehearsal
(``python -m pytest benchmarks/tests -q`` from the repo root), not part
of tier-1's ``tests/``.

They run on the CPU: four virtual devices for the mesh cell, Pallas
kernels in interpret mode, no persistent compile cache. A number from
such a run says nothing about a chip and is asserted on only as far as
plumbing goes.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=4"
# XLA's host-feature detection over-reports in this sandbox
# (tests/conftest.py); AVX2 is safe everywhere
if "xla_cpu_max_isa" not in flags:
    flags += " --xla_cpu_max_isa=AVX2"
os.environ["XLA_FLAGS"] = flags.strip()
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# the fused-scan driver is the TPU default; this existing switch of the
# program turns it on for the CPU backend
os.environ["LGBM_TPU_FUSE_ITERS"] = "1"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
