"""Structured training telemetry: spans, counters, per-iteration records.

One process-wide :class:`Telemetry` instance (``get_telemetry()``)
collects

  * hierarchical **spans** — named wall-clock regions that nest
    (``with tel.span("train"): ...``) and accumulate per dotted path;
    a span can open a named ``jax.profiler`` trace region. The
    set-up spans (``setup_span``; names in ``scopes.py``) and
    ``train`` also leave one ``span`` record each, with when they
    started and ended, so that set-up reads as a timeline;
  * typed **counters / gauges / distributions** — plain host floats
    (rows binned, histogram builds, collective payload bytes, ...);
  * **per-iteration records** — phase wall times (grad/grow/tree/
    update) accumulated by ``span(..., phase=True)`` between iteration
    boundaries, flushed by ``end_iteration``;
  * **compile accounting** — a ``jax.monitoring`` duration listener
    feeds ``jit.compiles`` / ``jit.compile_s`` (and trace/lowering
    seconds), separating compile time from steady-state throughput,
    and leaves one ``compile`` record a stage of every registered or
    long compile: program, stage, start and end, persistent-cache hit
    or miss, and the span it happened under.

Records flow to pluggable sinks: an in-memory ring buffer, a JSONL
file (``LGBM_TPU_TELEMETRY=/path`` env or the ``telemetry_out`` config
parameter), and a verbosity-honoring summary printer.

Cost model: when disabled, every hook is a single attribute check and
``span()`` returns a shared no-op context manager — no host syncs and
no extra device->host transfers are ever issued by this module; phase
spans measure HOST wall time around dispatches and values recorded at
iteration boundaries are already materialized by the caller.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ..utils import jit_registry
from ..utils.log import get_verbosity, log_info, log_warning
from .tracing import get_tracer

# jax.monitoring event suffixes -> (count counter, seconds counter,
# the ``compile`` record's stage). backend_compile is THE compile;
# trace/lowering are recorded too so a trace-dominated workload is
# visible as such.
_COMPILE_EVENTS = {
    "backend_compile_duration": ("jit.compiles", "jit.compile_s",
                                 "backend"),
    "jaxpr_trace_duration": ("jit.traces", "jit.trace_s", "trace"),
    "jaxpr_to_mlir_module_duration": ("jit.lowerings", "jit.lowering_s",
                                      "lower"),
}
# a stage of a program the registry does not know gets a ``compile``
# record from this many seconds on: jax's own primitives
# (``jit(convert_element_type)``) compile by the hundred in a few
# milliseconds each, and the ring is bounded
COMPILE_RECORD_MIN_S = 0.05

# plain (no-duration) jax.monitoring events worth counting: persistent
# compilation-cache traffic, so a warmed cache is visible as hits
# rather than inferred from a compile_s drop alone
_PLAIN_EVENTS = {
    "cache_hits": "jit.cache_hits",
    "cache_misses": "jit.cache_misses",
}


class RingSink:
    """Bounded in-memory record buffer (the default sink)."""

    def __init__(self, maxlen: int = 4096):
        self._buf: deque = deque(maxlen=maxlen)

    def emit(self, rec: Dict[str, Any]) -> None:
        self._buf.append(rec)

    @property
    def records(self) -> List[Dict[str, Any]]:
        return list(self._buf)

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


# record kinds worth an immediate file flush: run/summary boundaries
# are rare and losing them to buffer timing makes short CLI runs and
# preempted runs undiagnosable
_FLUSH_KINDS = ("run_start", "train_end", "serving_stats", "probe")


class JsonlSink:
    """Append-mode JSONL file sink; one record per line.

    Trailing-record durability: boundary records (``_FLUSH_KINDS``)
    flush immediately, and the module registers ONE process-wide
    ``atexit`` flush (plus the preemption handler's signal-time flush,
    robustness/preempt.py) so short CLI runs and preempted runs no
    longer lose whatever happened to sit in the stdio buffer."""

    def __init__(self, path: str):
        self.path = path
        self._fh = None

    def _ensure(self):
        if self._fh is None:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            self._fh = open(self.path, "a")
        return self._fh

    def emit(self, rec: Dict[str, Any]) -> None:
        try:
            self._ensure().write(json.dumps(rec, default=_json_default)
                                 + "\n")
            if rec.get("kind") in _FLUSH_KINDS:
                self._fh.flush()
        except OSError as e:  # telemetry must never kill training
            log_warning(f"telemetry sink write failed: {e}")

    def flush(self) -> None:
        if self._fh is not None:
            try:
                self._fh.flush()
            except OSError:
                pass

    def close(self) -> None:
        if self._fh is not None:
            try:
                self._fh.close()
            except OSError:
                pass
            self._fh = None


class SummarySink:
    """Prints a one-shot summary on ``train_end`` records, honoring the
    ``verbosity`` parameter (silent below verbosity 1)."""

    def emit(self, rec: Dict[str, Any]) -> None:
        if rec.get("kind") != "train_end" or get_verbosity() < 1:
            return
        parts = [f"{rec.get('iters', '?')} iters in "
                 f"{rec.get('dur_s', 0.0):.3f}s"]
        if rec.get("rows_per_s"):
            parts.append(f"{rec['rows_per_s'] / 1e6:.3f} Mrow-iters/s")
        comp = rec.get("compile") or {}
        if comp.get("count"):
            parts.append(f"compile {comp['count']}x "
                         f"{comp.get('seconds', 0.0):.2f}s")
        log_info("[telemetry] " + ", ".join(parts))
        phases = rec.get("phase_totals") or {}
        if phases:
            tot = sum(phases.values()) or 1.0
            body = "  ".join(f"{k}={v:.3f}s({100 * v / tot:.0f}%)"
                             for k, v in sorted(phases.items(),
                                                key=lambda kv: -kv[1]))
            log_info(f"[telemetry] phases: {body}")

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


def _json_default(o):
    try:
        return float(o)
    except (TypeError, ValueError):
        return str(o)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **fields) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """Active span: telemetry accumulation + optional jax profiler
    trace region + the trace-correlation bridge (every telemetry span
    lands on the tracing.py timeline with ids when the tracer is
    enabled — the training side of the end-to-end trace plane rides
    this, no second instrumentation pass). A ``ledger`` span also
    leaves a ``span`` record when it closes."""

    __slots__ = ("tel", "name", "phase", "trace", "ledger", "fields",
                 "_t0", "_path", "_ann", "_tspan")

    def __init__(self, tel: "Telemetry", name: str, phase: bool,
                 trace: Optional[str], ledger: bool,
                 fields: Dict[str, Any], tracer):
        self.tel = tel
        self.name = name
        self.phase = phase
        self.trace = trace
        self.ledger = ledger
        self.fields = fields
        self._ann = None
        self._tspan = None if tracer is None \
            else tracer._begin(name, "train", None, None, scoped=True)

    def set(self, **fields) -> None:
        """Fields of the ``span`` record known only once the work is
        under way (the plan a learner settled on)."""
        self.fields.update(fields)

    def __enter__(self):
        tel = self.tel
        if tel._enabled:
            stack = tel._stack()
            stack.append(self.name)
            self._path = "/".join(stack)
        else:
            self._path = None
        if self.trace is not None:
            from ..utils.log import annotate
            self._ann = annotate(self.trace)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        dur = t1 - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._tspan is not None:
            self._tspan.finish(**self.fields)
        tel = self.tel
        if self._path is not None and tel._enabled:
            stack = tel._stack()
            if stack and stack[-1] == self.name:
                stack.pop()
            with tel._lock:
                acc = tel.spans.setdefault(self._path, [0.0, 0])
                acc[0] += dur
                acc[1] += 1
                if self.phase:
                    tel._iter_phases[self.name] = \
                        tel._iter_phases.get(self.name, 0.0) + dur
            if self.ledger:
                tel.record("span", name=self.name, path=self._path,
                           parent=self._path.rpartition("/")[0] or None,
                           t0=self._t0, t1=t1, dur_s=round(dur, 6),
                           **self.fields)
        return False


class Telemetry:
    """Process-wide telemetry aggregator. See module docstring."""

    def __init__(self):
        self._enabled = False
        # serving's flusher + worker threads and the jax.monitoring
        # compile listener mutate the counter/gauge/dist dicts
        # concurrently with the training thread; one process-wide lock
        # keeps the read-modify-write increments from losing updates
        self._lock = threading.Lock()
        self._sinks: list = []
        self._ring: Optional[RingSink] = None
        # open span names and the compile listener's cache slot, a
        # thread its own: a span opened on the serving flusher's
        # thread is no child of the training thread's
        self._tls = threading.local()
        self.spans: Dict[str, list] = {}      # path -> [total_s, count]
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, Any] = {}
        self.dists: Dict[str, list] = {}      # name -> [n, sum, min, max]
        self._iter_phases: Dict[str, float] = {}
        self._iter_counts: Dict[str, float] = {}
        self._t0 = time.perf_counter()
        self._run_started = False
        self._listener_installed = False
        self.last_iter: Optional[Dict[str, Any]] = None

    # -- lifecycle -----------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def configure(self, jsonl_path: Optional[str] = None,
                  ring: int = 4096, summary: bool = True) -> "Telemetry":
        """(Re)build the sink list and enable collection."""
        for s in self._sinks:
            s.close()
        self._sinks = []
        self._ring = RingSink(maxlen=ring)
        self._sinks.append(self._ring)
        if jsonl_path:
            self._sinks.append(JsonlSink(jsonl_path))
        if summary:
            self._sinks.append(SummarySink())
        self._enabled = True
        self._t0 = time.perf_counter()
        self._install_compile_listener()
        _install_atexit_flush()
        return self

    def ensure_started(self, config=None) -> None:
        """Idempotent env/config-driven startup: enables collection when
        ``LGBM_TPU_TELEMETRY`` (env) or ``telemetry_out`` (config/CLI)
        names a JSONL path, and emits the one-time ``run_start`` record.
        Called from every training entry point; a no-op when neither
        knob is set and telemetry was not enabled programmatically."""
        # the trace-correlation plane (tracing.py) shares this seam:
        # trace_out / LGBM_TPU_TRACE and the profiler window arm here,
        # so every entry point that starts telemetry starts tracing
        get_tracer().ensure_started(config)
        path = (getattr(config, "telemetry_out", "") or "").strip() \
            or os.environ.get("LGBM_TPU_TELEMETRY", "").strip()
        if not self._enabled:
            if not path:
                return
            self.configure(jsonl_path=path)
        elif path and not any(isinstance(s, JsonlSink)
                              for s in self._sinks):
            # ring-only mode can be enabled first (a record_telemetry
            # callback, bench warm-up); an env/config JSONL path must
            # still attach its sink instead of being silently dropped
            self._sinks.append(JsonlSink(path))
            if not any(isinstance(s, SummarySink) for s in self._sinks):
                self._sinks.append(SummarySink())
        if not self._run_started:
            self._run_started = True
            self.record("run_start", **_run_meta(config))

    def ensure_ring(self, ring: int = 4096) -> None:
        """Enable ring-buffer-only collection (no file) when telemetry
        is off — used by the ``record_telemetry`` callback and bench so
        counters/records exist without any env/config opt-in."""
        if not self._enabled:
            self.configure(jsonl_path=None, ring=ring, summary=False)

    def disable(self) -> None:
        self.flush()
        for s in self._sinks:
            s.close()
        self._enabled = False
        self._run_started = False

    def reset(self) -> None:
        """Test helper: drop all accumulated state and sinks."""
        self.disable()
        self.__init__()

    # -- spans ---------------------------------------------------------
    def _stack(self) -> List[str]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_path(self) -> Optional[str]:
        """Path of the innermost span open on this thread."""
        return "/".join(self._stack()) or None

    @contextlib.contextmanager
    def under(self, path: Optional[str]):
        """Spans opened on this thread inside the block are children of
        ``path``, a span open on another thread: a worker's part of its
        caller's phase (``current_path()`` as the caller read it)."""
        stack = self._stack()
        saved = stack[:]
        stack[:] = path.split("/") if path else []
        try:
            yield
        finally:
            stack[:] = saved

    def span(self, name: str, phase: bool = False,
             trace: Optional[str] = None, ledger: bool = False,
             **fields):
        """Timed region. ``phase=True`` also accumulates the duration
        into the current iteration's phase table; ``trace=<name>`` opens
        a named jax profiler region. ``ledger=True`` also emits one
        ``span`` record when the region closes (``name``, ``path``,
        ``parent``, ``t0`` and ``t1`` as ``time.perf_counter()`` read
        them, ``dur_s`` and ``fields``); such a span is the shared
        no-op whenever telemetry and the tracer are both off, named
        profiler region or not."""
        tracer = get_tracer()
        if not self._enabled and not tracer._enabled \
                and (trace is None or ledger):
            return _NULL_SPAN
        return _Span(self, name, phase, trace, ledger, fields,
                     tracer if tracer._enabled else None)

    def setup_span(self, name: str, **fields):
        """One phase of table construction or booster set-up (the
        names are ``observability/scopes.py``'s): a ledger span that
        is also a named profiler region."""
        return self.span(name, trace=name, ledger=True, **fields)

    # -- metrics -------------------------------------------------------
    def count(self, name: str, value: float = 1.0) -> None:
        if self._enabled:
            v = float(value)
            with self._lock:
                self.counters[name] = self.counters.get(name, 0.0) + v

    def set_counter(self, name: str, value: float) -> None:
        """A counter that states a fact of the newest object of its
        kind (a constructed dataset's ``data.bundle_*``) and is
        written whole, not added to."""
        if self._enabled:
            with self._lock:
                self.counters[name] = float(value)

    def count_iter(self, name: str, value: float = 1.0) -> None:
        """Counter that ALSO accumulates into the current iteration's
        ``counts`` table (flushed into the ``iter`` record by
        ``end_iteration``, like phase spans). Used for the dispatch/
        host-sync accounting: ``host.dispatches`` counts device-program
        launches our training loop issues, ``host.syncs`` counts
        blocking device->host fetches. Both are counted at the call
        sites in models/gbdt.py and learner/*, NOT inferred — a site
        the loop stops issuing simply stops being counted."""
        if self._enabled:
            v = float(value)
            with self._lock:
                self.counters[name] = self.counters.get(name, 0.0) + v
                self._iter_counts[name] = \
                    self._iter_counts.get(name, 0.0) + v

    def gauge(self, name: str, value) -> None:
        if self._enabled:
            with self._lock:
                self.gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        if self._enabled:
            v = float(value)
            with self._lock:
                d = self.dists.get(name)
                if d is None:
                    self.dists[name] = [1, v, v, v]
                else:
                    d[0] += 1
                    d[1] += v
                    d[2] = min(d[2], v)
                    d[3] = max(d[3], v)

    def counter_state(self) -> Tuple[Dict[str, float], Dict[str, Any]]:
        """Consistent (counters, gauges) copies under one lock hold —
        the federation client's snapshot source (metrics.py
        ``FederationClient``); also handy for tests."""
        with self._lock:
            return dict(self.counters), dict(self.gauges)

    # -- records -------------------------------------------------------
    def record(self, kind: str, **fields) -> None:
        if not self._enabled:
            return
        rec: Dict[str, Any] = {
            "kind": kind,
            "t": round(time.perf_counter() - self._t0, 6)}
        rec.update(fields)
        for s in self._sinks:
            s.emit(rec)

    def end_iteration(self, iteration: int, **fields) -> None:
        """Close one boosting iteration: emits an ``iter`` record with
        the phase wall times accumulated since the previous boundary.
        Call only at iteration boundaries — the fields passed must
        already be host values (no device syncs are issued here)."""
        if not self._enabled:
            return
        with self._lock:
            phases = {k: round(v, 6)
                      for k, v in self._iter_phases.items()}
            self._iter_phases = {}
            counts = {k: v for k, v in self._iter_counts.items()}
            self._iter_counts = {}
        # feed the live metrics plane (observability/metrics.py): the
        # per-iteration phase wall times become the
        # train_phase_seconds{phase=...} histogram a /metrics scrape
        # can derive p50/p95/p99 from
        try:
            from .metrics import get_metrics
            reg = get_metrics()
            for name, dur in phases.items():
                reg.observe("train_phase_seconds", dur,
                            labels={"phase": name})
        except Exception:  # metrics must never kill an iteration
            pass
        rec = dict(iter=int(iteration), phases=phases, **fields)
        if counts:
            rec["counts"] = counts
        self.last_iter = rec
        self.record("iter", **rec)

    def eval_results(self, iteration: int, results) -> None:
        """Emit one ``eval`` record: [[dataset, metric, value,
        bigger_is_better], ...] at an iteration boundary."""
        if not self._enabled or not results:
            return
        self.record("eval", iter=int(iteration),
                    results=[[r[0], r[1], float(r[2]), bool(r[3])]
                             for r in results])

    def phase_totals(self) -> Dict[str, float]:
        """Per-phase totals across all iterations so far (seconds),
        derived from phase spans at any depth."""
        out: Dict[str, float] = {}
        for path, (tot, _cnt) in self.spans.items():
            name = path.rsplit("/", 1)[-1]
            if name in ("grad", "grow", "tree", "update", "eval",
                        "hist", "split", "partition"):
                out[name] = out.get(name, 0.0) + tot
        return {k: round(v, 6) for k, v in out.items()}

    def compile_stats(self) -> Dict[str, float]:
        return {"count": int(self.counters.get("jit.compiles", 0)),
                "seconds": round(self.counters.get("jit.compile_s",
                                                   0.0), 6),
                "trace_seconds": round(self.counters.get("jit.trace_s",
                                                         0.0), 6),
                "cache_hits": int(self.counters.get("jit.cache_hits",
                                                    0))}

    @property
    def records(self) -> List[Dict[str, Any]]:
        return self._ring.records if self._ring is not None else []

    def flush(self) -> None:
        for s in self._sinks:
            s.flush()

    # -- jax compile-time hook -----------------------------------------
    def _install_compile_listener(self) -> None:
        _install_compile_listener()


def _run_meta(config=None) -> Dict[str, Any]:
    meta: Dict[str, Any] = {"pid": os.getpid(),
                            "wall_time": time.time()}
    try:
        import jax
        meta["backend"] = jax.default_backend()
        meta["device_count"] = jax.device_count()
        meta["jax_version"] = jax.__version__
    except Exception:  # pragma: no cover
        pass
    if config is not None:
        keys = ("objective", "tree_learner", "num_leaves",
                "num_iterations", "learning_rate", "max_bin",
                "bagging_fraction", "bagging_freq", "feature_fraction",
                "num_class", "boosting")
        meta["config"] = {k: getattr(config, k) for k in keys
                          if hasattr(config, k)}
    return meta


def memory_snapshot() -> Dict[str, Any]:
    """Live-array census + per-device memory stats, for end-of-train
    records (NOT per-iteration: ``jax.live_arrays`` walks every live
    buffer)."""
    out: Dict[str, Any] = {}
    try:
        import jax
        arrs = jax.live_arrays()
        out["live_arrays"] = len(arrs)
        out["live_bytes"] = int(sum(
            a.size * a.dtype.itemsize for a in arrs
            if hasattr(a, "size") and hasattr(a, "dtype")))
        stats = jax.devices()[0].memory_stats()   # None on the CPU
        if stats:
            out["device_bytes_in_use"] = int(
                stats.get("bytes_in_use", 0))
            if "peak_bytes_in_use" in stats:
                out["device_peak_bytes"] = int(
                    stats["peak_bytes_in_use"])
    except Exception:  # memory stats are best-effort on every backend
        pass
    return out


def traced_bytes(tree) -> int:
    """Static payload size (bytes) of an array or pytree — works on
    tracers (shape/dtype are abstract-value attributes), so collective
    payloads can be counted at TRACE time with zero runtime cost."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * dtype.itemsize
    return total


_TELEMETRY = Telemetry()
_LISTENER_INSTALLED = [False]
_ATEXIT_INSTALLED = [False]


def _atexit_flush() -> None:
    """Interpreter-exit flush of the singleton's sinks, so a short CLI
    run never loses trailing records to buffer timing. Also invoked
    from the preemption signal handler (flush() is async-signal-safe
    enough: pure-Python file flushes, no locks held across it)."""
    tel = _TELEMETRY
    if tel._enabled:
        try:
            tel.flush()
        except Exception:  # interpreter may be tearing down
            pass


def _install_atexit_flush() -> None:
    if not _ATEXIT_INSTALLED[0]:
        _ATEXIT_INSTALLED[0] = True
        atexit.register(_atexit_flush)


def _program_of(fun_name: Optional[str]) -> Tuple[str, bool]:
    """``(program, registered)`` of a compile event's ``fun_name``:
    jax's name without its ``jit(...)``, or the name the program is
    registered under (``utils/jit_registry.py``) where the registry
    holds a callable of jax's name (``_grow_partitioned`` is
    ``partitioned_grow``)."""
    fun = str(fun_name or "")
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]
    if jit_registry.get(fun) is not None:
        return fun, True
    for name, prog in jit_registry.programs().items():
        if getattr(prog.fn, "__name__", None) == fun:
            return name, True
    return fun, False


def _install_compile_listener() -> None:
    """Register ONE process-wide jax.monitoring duration listener that
    feeds the singleton's compile counters and ``compile`` records
    (jax has no unregister, so installation must survive
    Telemetry.reset without stacking)."""
    if _LISTENER_INSTALLED[0]:
        return
    _LISTENER_INSTALLED[0] = True
    try:
        import jax.monitoring as monitoring

        def _listener(event: str, duration: float, **kw) -> None:
            tel = _TELEMETRY
            if not tel._enabled:
                return
            tail = event.rsplit("/", 1)[-1]
            names = _COMPILE_EVENTS.get(tail)
            if names is None:
                return
            now = time.perf_counter()   # jax calls as the stage ends
            tel.count(names[0], 1)
            tel.count(names[1], duration)
            fields: Dict[str, Any] = {}
            if names[2] == "backend":
                # the persistent cache says hit or miss on this thread
                # inside the compile it belongs to; neither where
                # there is no cache or the entry is too small to keep
                fields["cache"] = getattr(tel._tls, "cache", None) \
                    or "none"
                tel._tls.cache = None
            program, registered = _program_of(kw.get("fun_name"))
            if registered or duration >= COMPILE_RECORD_MIN_S:
                tel.record("compile", program=program, stage=names[2],
                           t0=now - duration, t1=now,
                           dur_s=round(duration, 6),
                           parent=tel.current_path(), **fields)

        monitoring.register_event_duration_secs_listener(_listener)

        def _plain_listener(event: str, **kw) -> None:
            tel = _TELEMETRY
            if not tel._enabled:
                return
            tail = event.rsplit("/", 1)[-1]
            name = _PLAIN_EVENTS.get(tail)
            if name is not None:
                tel.count(name, 1)
                tel._tls.cache = "hit" if tail == "cache_hits" else "miss"

        monitoring.register_event_listener(_plain_listener)
    except Exception as e:  # pragma: no cover - jax API drift
        log_warning(f"telemetry compile hook unavailable: {e}")


def get_telemetry() -> Telemetry:
    return _TELEMETRY


def telemetry_enabled() -> bool:
    return _TELEMETRY._enabled
