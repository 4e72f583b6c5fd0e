"""repro.obs.trace — context-manager spans exporting Chrome trace-event
JSON (loadable in ``chrome://tracing`` / Perfetto).

Standard library only at import: spans stamp a MONOTONIC wall clock
(``time.perf_counter_ns``) relative to the recorder's epoch and append
plain dicts in the Chrome trace-event format — complete events
(``ph="X"`` with ``ts``/``dur`` in microseconds) for spans, ``ph="i"``
instants, ``ph="M"`` metadata (thread names). ``export()`` writes the
``{"traceEvents": [...]}`` container.

Two timebases coexist in exported traces (the repo-wide contract — see
``repro.obs.__init__``):

* **wall spans** (:meth:`Trace.span`) measure real elapsed time on the
  monotonic clock — engine phases (admission, prefill dispatch, decode
  dispatch, block-until-ready) and trainer step phases (data, dispatch,
  sync, readback). This is what an SLO means.
* **tick spans** (:meth:`Trace.event` with explicit ``ts``/``dur``) are
  laid out on a deterministic timeline by the caller — the serve engine
  plots per-request lifecycles (queued → prefill → decode) at 1 engine
  tick = :data:`TICK_US` microseconds, so span geometry reproduces tick
  TTFT exactly and the trace is byte-stable across runs. Tick spans carry
  their tick stamps in ``args`` too.

Every wall span records its own ``id`` and the ``parent`` id of the span
open around it on the same thread (a per-thread stack), so a span's self
time is its duration less its children's. A recorder keeps the newest
:data:`CAPACITY` events (a ring): a long run never grows it without limit.

Each wall span also opens a ``jax.profiler.TraceAnnotation`` of the same
name (a ``StepTraceAnnotation`` for a span given ``step_num``), so under a
``jax.profiler`` session the span lands in the trace's host plane, on the
device trace's clock. Without a session an annotation costs about a
microsecond. jax is imported on the first span, never at import.

The process recorder (:func:`get_trace`) is on by default. The trainer
and the tile-table build record into it, and so does one process-wide
``jax.monitoring`` listener, installed by the first :func:`get_trace`:
each jit trace, lowering and backend compile (or persistent-cache load)
becomes a ``jax.trace`` / ``jax.lower`` / ``jax.compile`` event with its
``fun_name``, and is counted on the process registry as
``jax.compiles{kind=trace|lower|compile|cache_hit}``. A disabled recorder
(``Trace(enabled=False)``, or ``get_trace().enabled = False``) turns
``span()`` into a shared no-op context manager — hot loops pay one
attribute check.

``jax.profiler`` sessions are OPTIONAL and gated: pass
``jax_profile_dir=...`` and :meth:`start`/:meth:`stop` bracket a
``jax.profiler`` trace session alongside the span recording.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

#: tick-timeline scale: 1 engine clock tick = 1000us in exported traces
TICK_US = 1000
#: events a recorder keeps, newest last; older ones are dropped
CAPACITY = 1 << 16

_REQUIRED_KEYS = {"name", "ph", "ts", "pid", "tid"}


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_trace", "name", "cat", "tid", "args", "step_num", "_ann",
                 "_id", "_parent", "_t0")

    def __init__(self, trace: "Trace", name: str, cat: str, tid: Optional[int],
                 args: Optional[Dict[str, Any]], step_num: Optional[int]):
        self._trace = trace
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self.step_num = step_num

    def __enter__(self):
        tr = self._trace
        stack = tr._stack()
        self._parent = stack[-1] if stack else None
        self._id = next(tr._ids)
        stack.append(self._id)
        self._ann = _annotation(self.name, self.step_num)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        tr = self._trace
        tr._stack().pop()
        tr._complete(self.name, self.cat, self._t0, t1, self.tid, self.args,
                     self._id, self._parent)
        return False


_ANNOTATIONS = None


def _annotation(name: str, step_num: Optional[int]):
    """A ``jax.profiler`` (Step)TraceAnnotation of ``name``; a no-op where
    jax is not importable."""
    global _ANNOTATIONS
    if _ANNOTATIONS is None:
        try:
            from jax.profiler import StepTraceAnnotation, TraceAnnotation
            _ANNOTATIONS = (TraceAnnotation, StepTraceAnnotation)
        except ImportError:
            _ANNOTATIONS = ()
    if not _ANNOTATIONS:
        return _NULL_SPAN
    if step_num is None:
        return _ANNOTATIONS[0](name)
    return _ANNOTATIONS[1](name, step_num=step_num)


def _tid() -> int:
    return threading.get_ident() & 0x7FFFFFFF


class Trace:
    """Span recorder. All mutation goes through ``_append`` (locked);
    the newest :data:`CAPACITY` events stay in memory until
    :meth:`export`."""

    def __init__(self, enabled: bool = True, *,
                 jax_profile_dir: Optional[str] = None):
        self.enabled = enabled
        self.pid = os.getpid()
        self._epoch_ns = time.perf_counter_ns()
        self._events: collections.deque = collections.deque(maxlen=CAPACITY)
        self._lock = threading.Lock()
        # span ids: next() on a count is atomic under the interpreter lock
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._jax_profile_dir = jax_profile_dir
        self._profiling = False

    # -- recording --------------------------------------------------------
    def span(self, name: str, cat: str = "", tid: Optional[int] = None, *,
             step_num: Optional[int] = None, **args):
        """Context manager: one complete ("X") event on the wall clock.
        With ``step_num`` the span is a step: its profiler annotation is a
        ``StepTraceAnnotation`` and its event carries ``args.step``."""
        if not self.enabled:
            return _NULL_SPAN
        if step_num is not None:
            args["step"] = step_num
        return _Span(self, name, cat, tid, args or None, step_num)

    def complete(self, name: str, t0_ns: int, t1_ns: int, cat: str = "",
                 **args) -> None:
        """Record a span measured elsewhere, from ``t0_ns`` to ``t1_ns`` on
        the recorder's clock (``time.perf_counter_ns``), as a child of the
        span open on the calling thread."""
        if not self.enabled:
            return
        stack = self._stack()
        self._complete(name, cat, t0_ns, t1_ns, None, args or None,
                       next(self._ids), stack[-1] if stack else None)

    def _stack(self) -> List[int]:
        """The ids of the calling thread's open spans, innermost last."""
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _complete(self, name, cat, t0_ns, t1_ns, tid, args, sid,
                  parent) -> None:
        t0_ns = max(t0_ns, self._epoch_ns)
        ev = {"name": name, "cat": cat or "span", "ph": "X",
              "ts": (t0_ns - self._epoch_ns) / 1e3,
              "dur": max(t1_ns - t0_ns, 0) / 1e3, "pid": self.pid,
              "tid": tid if tid is not None else _tid(), "id": sid}
        if parent is not None:
            ev["parent"] = parent
        if args:
            ev["args"] = args
        self._append(ev)

    def instant(self, name: str, cat: str = "", **args) -> None:
        if not self.enabled:
            return
        self._append({
            "name": name, "cat": cat or "instant", "ph": "i", "s": "t",
            "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
            "pid": self.pid, "tid": _tid(),
            **({"args": args} if args else {}),
        })

    def event(self, name: str, *, ts_us: float, dur_us: float,
              tid: int, cat: str = "",
              args: Optional[Dict[str, Any]] = None) -> None:
        """Append a complete event at an EXPLICIT position — the caller
        owns the timeline (the serve engine lays request lifecycles out on
        the tick clock at :data:`TICK_US` us/tick)."""
        if not self.enabled:
            return
        self._append({
            "name": name, "cat": cat or "span", "ph": "X",
            "ts": float(ts_us), "dur": float(dur_us),
            "pid": self.pid, "tid": int(tid),
            **({"args": args} if args else {}),
        })

    def thread_name(self, tid: int, label: str) -> None:
        """Metadata event: label a tid lane (e.g. one lane per request)."""
        if not self.enabled:
            return
        self._append({"name": "thread_name", "ph": "M", "ts": 0.0,
                      "pid": self.pid, "tid": int(tid),
                      "args": {"name": label}})

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(ev)

    # -- jax.profiler hooks (flag-gated) ----------------------------------
    def start(self) -> None:
        """Begin an optional ``jax.profiler`` session when constructed
        with ``jax_profile_dir`` (no-op otherwise)."""
        if self._jax_profile_dir and not self._profiling:
            import jax
            jax.profiler.start_trace(self._jax_profile_dir)
            self._profiling = True

    def stop(self) -> None:
        if self._profiling:
            import jax
            jax.profiler.stop_trace()
            self._profiling = False

    # -- export -----------------------------------------------------------
    @property
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def to_dict(self) -> Dict[str, Any]:
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> int:
        """Write the Chrome trace container; returns the event count."""
        doc = self.to_dict()
        with open(path, "w") as f:
            json.dump(doc, f, separators=(",", ":"))
            f.write("\n")
        return len(doc["traceEvents"])


def validate(doc) -> int:
    """Validate a trace document (or bare event list) against the Chrome
    trace-event schema subset this module emits: every event carries
    name/ph/ts/pid/tid, ``ts``/``dur`` are finite non-negative numbers,
    complete ("X") events carry ``dur``, metadata ("M") events carry
    ``args``. Raises ValueError on the first violation; returns the event
    count (> 0 — an empty trace is a wiring bug, not a trace)."""
    events = doc.get("traceEvents") if isinstance(doc, dict) else doc
    if not isinstance(events, list) or not events:
        raise ValueError("trace has no traceEvents list (or it is empty)")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i}: not an object: {ev!r}")
        missing = _REQUIRED_KEYS - ev.keys()
        if missing:
            raise ValueError(f"event {i} ({ev.get('name')!r}): missing "
                             f"required keys {sorted(missing)}")
        for k in ("ts", "dur"):
            if k in ev:
                v = ev[k]
                if not isinstance(v, (int, float)) or v < 0 or \
                        v != v or v in (float("inf"),):
                    raise ValueError(f"event {i} ({ev['name']!r}): {k}={v!r}"
                                     " not a finite non-negative number")
        if ev["ph"] == "X" and "dur" not in ev:
            raise ValueError(f"event {i} ({ev['name']!r}): complete event "
                             "without dur")
        if ev["ph"] == "M" and "args" not in ev:
            raise ValueError(f"event {i} ({ev['name']!r}): metadata event "
                             "without args")
        if "args" in ev and not isinstance(ev["args"], dict):
            raise ValueError(f"event {i} ({ev['name']!r}): args not an "
                             "object")
    return len(events)


def validate_file(path: str) -> int:
    """JSON-load ``path`` and :func:`validate` it (CI smoke entry point)."""
    with open(path) as f:
        return validate(json.load(f))


# ---------------------------------------------------------------------------
# The process recorder and JAX's compile events
# ---------------------------------------------------------------------------

_PROCESS = Trace()
_LISTENING = False
_LISTEN_LOCK = threading.Lock()

#: jax.monitoring time-span events -> (event name, ``jax.compiles`` kind)
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jax.trace", "trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("jax.lower", "lower"),
    "/jax/core/compile/backend_compile_duration": ("jax.compile", "compile"),
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def get_trace() -> Trace:
    """The process recorder. The first call installs the process-wide
    ``jax.monitoring`` listener that records JAX's compile events into it
    (module docstring)."""
    global _LISTENING
    with _LISTEN_LOCK:
        if not _LISTENING:
            _LISTENING = True
            _listen_to_jax()
    return _PROCESS


def _listen_to_jax() -> None:
    try:
        from jax import monitoring
    except ImportError:
        return
    from repro.obs.metrics import get_registry
    compiles = get_registry().counter(
        "jax.compiles", help="jit traces, lowerings, backend compiles "
        "(persistent-cache loads included) and persistent-cache hits")
    kinds = {kind: compiles.labels(kind=kind)
             for kind in ("trace", "lower", "compile", "cache_hit")}

    def on_event(event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            kinds["cache_hit"].inc()

    def on_time_span(event: str, start_s: float, end_s: float, **kw) -> None:
        named = JAX_EVENTS.get(event)
        if named is None:
            return
        name, kind = named
        kinds[kind].inc()
        # JAX stamps the span with time.time(); move it onto the
        # recorder's perf_counter clock through the two clocks' readings now
        now_ns, now_s = time.perf_counter_ns(), time.time()
        t1_ns = now_ns - int((now_s - end_s) * 1e9)
        _PROCESS.complete(name, t1_ns - int((end_s - start_s) * 1e9), t1_ns,
                          cat="jax", fun_name=str(kw.get("fun_name", "")))

    monitoring.register_event_listener(on_event)
    monitoring.register_event_time_span_listener(on_time_span)
