"""Probe failure taxonomy (ROADMAP item 6): structured reason codes.

This module classifies the raw cause of a failed accelerator probe
(bench.py) into a small stable vocabulary so the failure MODE is
diagnosable from telemetry (``tools/run_report.py`` renders the probe
timeline; the ``probe`` telemetry records carry ``reason_code``):

* ``no_device``     — jax came up but only saw CPU (the probe's
                      device assert).
* ``init_timeout``  — the probe child hung past its budget (backend
                      init never returned — e.g. another process
                      holds the chip).
* ``not_lowerable`` — Mosaic's lowering pass rejected a kernel.
* ``compile_error`` — devices were there but compilation/execution
                      failed (XLA/Mosaic errors).
* ``transport``     — connection-level failures reaching a remote
                      backend (refused/reset/unreachable/grpc
                      deadline).
* ``unknown``       — none of the signatures matched; the raw cause
                      is always attached alongside the code.

Stdlib-only: imported by the bench PARENT (which never imports jax —
a parent that had touched JAX would hold the chip its children need)
and by ``tools/run_report.py`` (which must render on boxes without
jax).
"""

from __future__ import annotations

REASON_CODES = ("no_device", "init_timeout", "not_lowerable",
                "compile_error", "transport", "unknown")

# process-fleet worker lifecycle codes (serving/procfleet.py): the
# supervisor classifies every worker death into this vocabulary so the
# run_report replica timeline and the chaos-soak artifacts are
# trendable the same way the TPU probe's failures are
WORKER_REASON_CODES = ("spawn_failed", "heartbeat_lost", "oom_killed",
                       "respawn_exhausted", "socket_lost",
                       "load_failed", "crashed", "exited")

_WORKER_SIGNATURES = (
    (("never said hello", "spawn failed", "worker spawn"),
     "spawn_failed"),
    (("no frame from", "heartbeat", "went quiet"), "heartbeat_lost"),
    (("exited with 137", "exited with -9", "oom", "out of memory",
      "resource_exhausted"), "oom_killed"),
    (("quarantin", "respawn budget", "restart budget",
      "respawn_exhausted"), "respawn_exhausted"),
    (("socket failed", "broken pipe", "connection reset",
      "socket_lost"), "socket_lost"),
)


# elastic distributed-training codes (robustness/elastic.py): the
# collective watchdog classifies every mid-train distributed failure
# into this vocabulary; the abort line every aborting rank prints
# (``ELASTIC_ABORT reason=<code> rank=<r> ...``) round-trips through
# classify_elastic_failure so drill harnesses and the run_report
# elastic timeline agree with the watchdog's verdict
ELASTIC_REASON_CODES = ("peer_lost", "collective_stall",
                        "coordinator_lost", "unknown")

_ELASTIC_SIGNATURES = (
    (("coordinator_lost", "coordinator went quiet",
      "coordinator heartbeat"), "coordinator_lost"),
    (("collective_stall", "no iteration boundary",
      "stall timeout"), "collective_stall"),
    (("peer_lost", "heartbeat connection closed",
      "heartbeats stale", "never joined"), "peer_lost"),
)


def classify_elastic_failure(detail: str) -> str:
    """Elastic abort evidence -> one of :data:`ELASTIC_REASON_CODES`.

    The explicit ``reason=<code>`` token (watchdog abort lines,
    telemetry records) wins; free-text evidence falls back to
    signature matching.
    """
    d = (detail or "").lower()
    if not d.strip():
        return "unknown"
    for tok in d.replace(",", " ").split():
        if tok.startswith("reason="):
            code = tok[len("reason="):]
            if code in ELASTIC_REASON_CODES:
                return code
    for needles, code in _ELASTIC_SIGNATURES:
        if any(n in d for n in needles):
            return code
    return "unknown"


def classify_worker_failure(detail: str,
                            exit_code=None) -> str:
    """Worker death evidence -> one of :data:`WORKER_REASON_CODES`.

    ``exit_code`` (Popen returncode) wins when decisive: 137 and
    SIGKILL are the OOM reaper's signature, any other signal is a
    crash. Free-text evidence (supervisor log detail, spawn errors)
    falls back to signature matching.
    """
    if exit_code is not None:
        code = int(exit_code)
        if code == 137 or code == -9:
            return "oom_killed"
        if code < 0 or code > 0:
            return "crashed"
    d = (detail or "").lower()
    for needles, code in _WORKER_SIGNATURES:
        if any(n in d for n in needles):
            return code
    return "crashed" if d.strip() else "exited"

# signature -> code, checked in order: the FIRST match wins, so the
# more specific transport/compile signatures are tested before the
# broad device-assert one
_SIGNATURES = (
    # the probe child hung past its timeout (bench.py writes this
    # exact detail) or the subprocess layer timed out
    (("hung > ", "timeoutexpired", "timed out", "deadline_exceeded",
      "initialization timed out"), "init_timeout"),
    # the kernel itself is rejected by the Mosaic LOWERING pass (a
    # capability gap, not a device/toolchain crash)
    (("loweringexception", "notimplementederror", "not implemented",
      "verificationerror"), "not_lowerable"),
    # reaching a remote backend failed at the connection level
    (("connection refused", "connection reset", "unreachable",
      "failed to connect", "socket",
      "grpc", "unavailable:", "broken pipe", "econnrefused"),
     "transport"),
    # devices came up; compiling/running the tiny program did not
    (("xlaruntimeerror", "compile", "mosaic", "lowering",
      "internal: ", "unimplemented"), "compile_error"),
    # the probe's assert fired: jax fell back to CPU / saw no chips
    (("platform != 'cpu'", "platform 'cpu'", "assertionerror",
      "no devices", "device_count", "cpudevice",
      "unable to initialize backend"), "no_device"),
)


def classify_probe_failure(detail: str) -> str:
    """Raw probe stderr/assert tail -> one of :data:`REASON_CODES`."""
    d = (detail or "").lower()
    if not d.strip():
        return "unknown"
    for needles, code in _SIGNATURES:
        if any(n in d for n in needles):
            return code
    return "unknown"


if __name__ == "__main__":  # tiny manual check: classify stdin
    import sys
    print(classify_probe_failure(sys.stdin.read()))
