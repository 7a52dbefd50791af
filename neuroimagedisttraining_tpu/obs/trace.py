"""Hierarchical host-side span tracer with Chrome trace-event output.

Spans are host wall-clock intervals (``with span("sample"):``) collected
as Chrome trace-event JSON — loadable in Perfetto / ``chrome://tracing``
— and each span also enters a ``jax.profiler.TraceAnnotation`` (rounds
use ``StepTraceAnnotation``) so that when a ``jax.profiler`` device
trace is captured in the same region (``--profile_dir`` /
``utils.profiling.trace``), the host spans line up with the XLA device
timeline in one view.

Disabled mode is a true no-op: the module-level tracer defaults to
:data:`NULL_TRACER`, whose ``span`` returns one shared singleton — no
string formatting, no dict churn, no timestamps on the hot path. Callers
therefore write ``with trace.span("name") as sp: ... sp.add(k, v)``
unconditionally; the whole construct costs two dynamic dispatches per
span when tracing is off.

Span timing caveat (JAX async dispatch): a host span around a jitted
call measures DISPATCH time unless the caller synchronizes — which the
round loop deliberately does not (utils/records.DeferredRecords). Spans
around fused blocks therefore wrap the dispatch and the flush separately
(whole-block attribution, never a forced device sync inside the block).

One tree per process. Every event carries ``span_id`` (this tracer's own
sequence, in order of opening), ``parent`` (the id of the innermost span
open on the thread when it began, else None) and ``args["depth"]``; a
span opened with ``step_span(name, step)`` hands its step down, and every
event under it carries ``args["round"]``. ``Tracer.record`` adds an event
after the fact, for a duration something else measured (``obs/compile.py``'s
compile phases), as a child of the innermost open span. At the exit of a
depth-0 span, where the backend keeps ``memory_stats()``, the event's args
hold ``hbm_in_use_bytes`` and ``hbm_peak_bytes`` (the largest over the local
devices): the peak only rises, so its value at each phase's end says which
phase set it. ``Tracer.to_unix_ns(event)`` puts an event on the unix clock
(``origin_unix_ns`` is in the written file's metadata); the profiler's
``.xplane.pb`` counts its host events from the ``profile_start_time`` of its
``Task Environment`` plane, a unix time in ns.

The span names, string literals at their call sites, listed once here as the
scopes are in ``algorithms/base.py``. The benchmark's metric files ask for
them by name (``tests/test_span_tree.py`` holds the two lists together): a
rename is an edit to both. The benchmark's harness names five of its own
(``cohort``, ``build``, ``init_state``, ``reference_check``, ``warmup``) and
keys them by name: no span on a path it calls may take one of those.

    import_program     the package's own import, stamped in its __init__ and
                       recorded by the first set_tracer
    init_params        base.init_model_params: the jitted model.init
    snip_mask          salientgrads.init_state: the SNIP pass
    place_state        base.place_state: the state replicated on a mesh
    expert_load        runner: the gauges' one forward
    run                base.FedAlgorithm.run, the whole call
    round              one round of run (a step span); the runner's loop
    sample             base._selected_client_indexes
    dispatch_round     the round program's dispatch (async)
    store_gather       base._store_gather_rows
    evaluate           run: self.evaluate(state), dispatches
    flush              utils/records.DeferredRecords.flush: the blocking
                       fetch of the pending record (args: its round)
    fused_block_dispatch, fused_block_flush   base._fused_block_loop
    eval, finalize, finetune    the runner's loop; fedavg.finalize
    compile/trace, compile/lower, compile/backend   obs/compile.py: each
                       jax.monitoring compile duration, recorded
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from .. import _IMPORT_END_NS, _IMPORT_START_NS

__all__ = [
    "NULL_TRACER", "NullSpan", "Tracer", "current_span_name",
    "get_tracer", "set_tracer", "span", "step_span", "tracing_enabled",
]


class NullSpan:
    """The shared disabled-mode span: every operation is a no-op."""

    __slots__ = ()

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def add(self, key: str, value: Any) -> None:
        """Per-span counter/attribute: dropped when tracing is off."""


_NULL_SPAN = NullSpan()


class NullTracer:
    """Disabled tracer: ``span`` hands back the shared :class:`NullSpan`
    without touching its arguments."""

    enabled = False

    def span(self, name: str, args: Optional[Dict[str, Any]] = None):
        return _NULL_SPAN

    def step_span(self, name: str, step: int):
        return _NULL_SPAN

    def record(self, name: str, start_ns: int, dur_ns: int,
               args: Optional[Dict[str, Any]] = None) -> None:
        """Dropped when tracing is off."""

    def current_span_name(self) -> str:
        return ""


NULL_TRACER = NullTracer()


def _hbm_bytes() -> Optional[Tuple[int, int]]:
    """``(bytes_in_use, peak_bytes_in_use)`` of the allocator, each the
    largest over the local devices; None where the backend keeps no
    ``memory_stats`` (the CPU's). No walk over the live arrays."""
    stats = [s for s in (d.memory_stats() for d in jax.local_devices()) if s]
    if not stats:
        return None
    return (max(s.get("bytes_in_use", 0) for s in stats),
            max(s.get("peak_bytes_in_use", 0) for s in stats))


class _Span:
    """One live span: a Chrome complete event ("ph": "X") in the making,
    mirrored into a ``jax.profiler`` annotation for device-trace
    alignment."""

    __slots__ = ("_tracer", "_name", "_args", "_step", "_t0", "_annotation",
                 "_id", "_parent", "_round")

    def __init__(self, tracer: "Tracer", name: str,
                 args: Optional[Dict[str, Any]], annotation,
                 step: Optional[int] = None) -> None:
        self._tracer = tracer
        self._name = name
        self._args = args
        self._step = step
        self._annotation = annotation
        self._t0 = 0

    def add(self, key: str, value: Any) -> None:
        """Attach a per-span counter/attribute (lands in the trace
        event's ``args``)."""
        if self._args is None:
            self._args = {}
        self._args[key] = value

    def __enter__(self) -> "_Span":
        if self._annotation is not None:
            self._annotation.__enter__()
        self._id, self._parent, self._round = self._tracer._push(
            self._name, self._step)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dur_ns = time.perf_counter_ns() - self._t0
        depth = self._tracer._pop()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if depth == 0:
            hbm = _hbm_bytes()
            if hbm is not None:
                self.add("hbm_in_use_bytes", hbm[0])
                self.add("hbm_peak_bytes", hbm[1])
        self._tracer._emit(self._name, self._t0, dur_ns, depth, self._args,
                           self._id, self._parent, self._round)
        return False


class Tracer:
    """Collects spans as Chrome trace events.

    ``annotate=True`` (default) also wraps each span in
    ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` for
    :meth:`step_span`) so host spans appear on the device trace when one
    is being captured. ``max_events`` bounds memory on long runs — once
    full, new spans still time correctly but stop appending (the count
    of dropped events is recorded in the written file).
    """

    enabled = True

    def __init__(self, annotate: bool = True,
                 max_events: int = 200_000) -> None:
        self._events: List[Dict[str, Any]] = []
        self._max_events = int(max_events)
        self._dropped = 0
        self._annotate = annotate
        self._local = threading.local()
        self._pid = os.getpid()
        # one origin so event timestamps are small relative microseconds;
        # the unix time beside it anchors them to other clocks (as XTracer)
        self._origin_ns = time.perf_counter_ns()
        self.origin_unix_ns = time.time_ns()
        self._ids = itertools.count(1)

    # -- the open spans (per thread) ------------------------------------
    # A stack of (name, span_id, round). It is the parent of whatever
    # begins next, and doubles as the compile-attribution context:
    # obs/compile.py labels jax compile events with the innermost open
    # span (the jitted entry point being dispatched).
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, name: str, step: Optional[int]) -> tuple:
        """Open a span: ``(span_id, parent, round)``, ``round`` what its
        ancestors handed down (its own ``step`` goes to its children)."""
        stack = self._stack()
        _, parent, round_ = stack[-1] if stack else ("", None, None)
        span_id = next(self._ids)
        stack.append((name, span_id, round_ if step is None else step))
        return span_id, parent, round_

    def _pop(self) -> int:
        stack = self._stack()
        if stack:
            stack.pop()
        return len(stack)  # depth of the closed span (0 = top)

    def current_span_name(self) -> str:
        """Innermost OPEN span on this thread ('' outside any span)."""
        stack = self._stack()
        return stack[-1][0] if stack else ""

    def _emit(self, name: str, t0_ns: int, dur_ns: int, depth: int,
              args: Optional[Dict[str, Any]], span_id: int,
              parent: Optional[int], round_: Optional[int]) -> None:
        if len(self._events) >= self._max_events:
            self._dropped += 1
            return
        ev_args = dict(args or ())
        ev_args["depth"] = depth
        if round_ is not None:
            ev_args.setdefault("round", round_)
        self._events.append({
            "name": name, "ph": "X",
            "ts": (t0_ns - self._origin_ns) / 1e3,   # microseconds
            "dur": dur_ns / 1e3,
            "pid": self._pid, "tid": threading.get_ident(),
            "span_id": span_id, "parent": parent, "args": ev_args,
        })

    # -- span construction ----------------------------------------------
    def span(self, name: str, args: Optional[Dict[str, Any]] = None):
        """Context manager timing a named host interval (nested spans
        stack by time containment in the viewer)."""
        annotation = (jax.profiler.TraceAnnotation(name)
                      if self._annotate else None)
        return _Span(self, name, args, annotation)

    def step_span(self, name: str, step: int):
        """A round/step-level span: ``StepTraceAnnotation`` marks step
        boundaries for the XLA trace's per-step grouping, and the spans
        under it record ``step`` as their ``round``."""
        annotation = (jax.profiler.StepTraceAnnotation(name, step_num=step)
                      if self._annotate else None)
        return _Span(self, name, {"step": int(step)}, annotation, int(step))

    def record(self, name: str, start_ns: int, dur_ns: int,
               args: Optional[Dict[str, Any]] = None) -> None:
        """An event after the fact, for a duration something else
        measured: ``start_ns`` on ``time.perf_counter_ns``'s clock. A
        child of the innermost span open on this thread now; it never
        samples memory."""
        stack = self._stack()
        _, parent, round_ = stack[-1] if stack else ("", None, None)
        self._emit(name, int(start_ns), int(dur_ns), len(stack), args,
                   next(self._ids), parent, round_)

    def to_unix_ns(self, event: Dict[str, Any]) -> int:
        """The start of ``event`` (one of :attr:`events`) on the unix
        clock."""
        return self.origin_unix_ns + round(event["ts"] * 1e3)

    # -- output ---------------------------------------------------------
    @property
    def events(self) -> List[Dict[str, Any]]:
        return self._events

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        meta: Dict[str, Any] = {"displayTimeUnit": "ms",
                                "origin_unix_ns": self.origin_unix_ns}
        if self._dropped:
            meta["obs_dropped_events"] = self._dropped
        return {"traceEvents": list(self._events), **meta}

    def write(self, path: str) -> str:
        """Write the trace to ``path`` (parent dirs created)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


# -- module-level active tracer ----------------------------------------
# The hot-path entry points: library code calls ``trace.span(name)``
# unconditionally; with no tracer installed this is one global read +
# one method call returning the shared NullSpan.

_active: Any = NULL_TRACER
_import_recorded = False   # the process's ``import_program`` event is out


def set_tracer(tracer: Optional[Any]) -> None:
    """Install ``tracer`` as the process-wide active tracer (None
    restores the null tracer). The runner installs its per-run tracer at
    session start and restores on exit. The first tracer a process
    installs also gets the package's own import as the event
    ``import_program``, so its tree starts where the program was reached."""
    global _active, _import_recorded
    _active = tracer if tracer is not None else NULL_TRACER
    if tracing_enabled() and not _import_recorded:
        _import_recorded = True
        tracer.record("import_program", _IMPORT_START_NS,
                      _IMPORT_END_NS - _IMPORT_START_NS)


def get_tracer():
    return _active


def tracing_enabled() -> bool:
    return bool(getattr(_active, "enabled", False))


def span(name: str, args: Optional[Dict[str, Any]] = None):
    """``with trace.span("sample"): ...`` on whatever tracer is active."""
    return _active.span(name, args)


def step_span(name: str, step: int):
    """``with trace.step_span("round", r): ...`` — step-annotated span."""
    return _active.step_span(name, step)


def current_span_name() -> str:
    """Innermost open span name on the active tracer ('' when tracing is
    off or outside any span) — the compile-attribution context."""
    return _active.current_span_name()
