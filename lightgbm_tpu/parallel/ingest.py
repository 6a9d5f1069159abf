"""Sharded dataset ingest: the binned matrix goes host -> mesh shards
directly, never through a replicated device copy.

Reference analog: ``pre_partition`` + the per-machine data loading of
``dataset_loader.cpp`` — each machine materializes only its own rows.
The TPU-native failure mode this module exists to kill is different:
a naive ``jnp.asarray(binned)`` stages the FULL matrix on the default
device (host 0's first chip) before ``device_put`` re-shards it, so a
100M-row binned matrix transits one HBM no matter how large the mesh
is. Every mesh learner routes its row-sharded arrays through
``shard_rows`` instead:

* single process — ONE ``jax.device_put(host_array, row_sharding)``;
  jax transfers each shard host->device individually, and no
  replicated device buffer ever exists;
* multi process — each host passes only its OWN row block
  (``local=True``) and the global array is assembled from the
  process-local shards (``jax.make_array_from_process_local_data``),
  so no host ever holds — let alone transfers — rows it does not own.

``host_row_range`` is the one definition of "which rows are mine" for
per-host ingest, and the telemetry counters (``ingest.sharded_bytes``,
``ingest.shards``) make the shard-local path auditable in any trace.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .partition_rules import AXIS, mesh_from_config, mesh_shards


def host_row_range(num_rows: int, process_index: Optional[int] = None,
                   process_count: Optional[int] = None
                   ) -> Tuple[int, int]:
    """[start, stop) of this host's row block for ``num_rows`` global
    rows split evenly over the processes (remainder rows go to the
    first ``num_rows % P`` hosts, matching the reference's
    pre-partition convention of contiguous per-machine blocks)."""
    p = jax.process_index() if process_index is None else process_index
    n = jax.process_count() if process_count is None else process_count
    base, rem = divmod(int(num_rows), n)
    start = p * base + min(p, rem)
    return start, start + base + (1 if p < rem else 0)


def _count_ingest(nbytes: int, shards: int, local: bool) -> None:
    from ..observability.telemetry import get_telemetry
    tel = get_telemetry()
    if tel.enabled:
        tel.count("ingest.sharded_bytes", float(nbytes))
        tel.count("ingest.sharded_puts", 1)
        tel.gauge("ingest.shards", shards)
        tel.gauge("ingest.local_build", int(bool(local)))


def shard_rows(arr, mesh: Mesh, *, axis: str = AXIS,
               local: bool = False, global_rows: Optional[int] = None):
    """Row-shard a HOST array over ``mesh`` without a replicated
    device copy.

    ``arr`` must be host-resident (numpy) with ``arr.shape[0]`` a
    multiple of the mesh size (callers pad rows first — padding rows
    carry zero gradient weight so they never affect training).

    ``local=True`` declares ``arr`` to be THIS process's row block
    only (``host_row_range`` order); ``global_rows`` then gives the
    global row count (default: local rows x process_count, the
    even-split case). Single-process runs ignore ``local``.
    """
    arr = np.asarray(arr)
    spec = P(axis, *([None] * (arr.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    _count_ingest(arr.nbytes, mesh_shards(mesh), local)
    if local and jax.process_count() > 1:
        n_global = int(global_rows) if global_rows is not None \
            else arr.shape[0] * jax.process_count()
        global_shape = (n_global,) + arr.shape[1:]
        return jax.make_array_from_process_local_data(
            sharding, arr, global_shape)
    # one call; jax transfers each shard host->device individually —
    # the host-0 path never materializes a replicated device matrix
    return jax.device_put(arr, sharding)


def pad_rows(arr: np.ndarray, n_pad: int) -> np.ndarray:
    """Host-side zero row padding to the mesh-divisible length (a
    numpy pad, NOT jnp.pad — padding on device would stage the full
    matrix through the default device first)."""
    arr = np.asarray(arr)
    n = arr.shape[0]
    if n_pad == n:
        return arr
    return np.pad(arr, ((0, n_pad - n),) + ((0, 0),) * (arr.ndim - 1))


def zeros_rows(shape, dtype, mesh: Mesh, *, axis: str = AXIS):
    """A row-sharded array of zeros made on the devices themselves:
    nothing crosses from the host (a scratch twin of a training
    matrix)."""
    import jax.numpy as jnp
    sharding = NamedSharding(mesh, P(axis, *([None] * (len(shape) - 1))))
    return jnp.zeros(shape, dtype, device=sharding)


def replicate(arr, mesh: Mesh):
    """Replicated placement (feature-parallel's row matrix: the
    algorithm requires every shard to hold all rows)."""
    return jax.device_put(np.asarray(arr),
                          NamedSharding(mesh, P()))


# -- a table built a row shard at a time ----------------------------------
def row_shards(config) -> int:
    """The row shards a booster on ``config`` trains over: the size of
    the data- and voting-parallel learners' mesh (``mesh_from_config``),
    1 for every other learner and where no such mesh can be made here.
    Table construction bins one shard a worker by it, so a one-shard
    table keeps one worker."""
    if config.tree_learner not in ("data", "voting"):
        return 1
    from ..utils.log import LightGBMError
    try:
        return mesh_shards(mesh_from_config(config))
    except LightGBMError:
        return 1


def shard_bounds(num_rows: int, d: int, s: int) -> Tuple[int, int]:
    """``[lo, hi)`` of shard ``s``'s rows: ``ceil(num_rows / d)`` a
    shard, the last ones short or empty (the mesh learners' padding,
    ``parallel/learners.py``)."""
    n_local = -(-int(num_rows) // d)
    lo = min(s * n_local, num_rows)
    return lo, min(lo + n_local, num_rows)


def per_shard(d: int, work: Callable[[int], None]) -> None:
    """``work(s)`` for every shard, one thread a shard (the caller's
    thread alone where ``d`` is 1); the first shard's error, if any, is
    raised once every thread has ended. Threads, not a pool: a pool
    hands a second shard to a thread that finished its first."""
    if d == 1:
        work(0)
        return
    errors: list = [None] * d

    def run(s: int) -> None:
        try:
            work(s)
        except Exception as e:      # noqa: BLE001 - raised below
            errors[s] = e
    threads = [threading.Thread(target=run, args=(s,)) for s in range(d)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for e in errors:
        if e is not None:
            raise e


def row_blocks_of(binned: np.ndarray, d: int) -> np.ndarray:
    """The segment learners' training matrices of ``d`` row shards,
    ``[d, rows_local, cols]`` u8 (``ops/hist_pallas.py``'s layout),
    filled a shard a worker: shard ``s`` holds the global rows
    ``shard_bounds(n, d, s)`` in its first rows, their bin bytes first
    and their GLOBAL row id in four bytes from ``width + RID_OFF``; its
    padding rows up to ``ceil(n / d)`` carry ids >= n."""
    from ..learner.partitioned import HIST_BLK
    from ..ops.hist_pallas import RID_OFF, matrix_cols, matrix_rows
    n, width = binned.shape
    n_local = -(-int(n) // d)
    mats = np.zeros((d, matrix_rows(n_local, HIST_BLK), matrix_cols(width)),
                    np.uint8)
    col = width + RID_OFF

    def fill(s: int) -> None:
        lo, hi = shard_bounds(n, d, s)
        mats[s, :hi - lo, :width] = binned[lo:hi]
        rid = (s * n_local + np.arange(n_local)).astype("<u4")
        mats[s, :n_local, col:col + 4] = rid.view(np.uint8).reshape(-1, 4)
    per_shard(d, fill)
    return mats
