"""Compile-time observability: where the first-round seconds went.

Every perf number in this repo excludes compile via warmup rounds, but
compile time itself is a real cost at the north-star scale (a fused
K-round program at C=32 compiles for minutes) and nothing recorded it.
Two complementary sources:

* :class:`CompileWatch` — listeners on ``jax.monitoring``'s compile
  events, feeding the obs registry: per-phase wall-time distributions
  (``compile_trace_s`` / ``compile_lower_s`` / ``compile_backend_s``),
  labeled by the innermost open obs span at the moment the compile
  fired (``obs.trace.current_span_name()``) — the jitted ENTRY POINT
  being dispatched (``dispatch_round``, ``eval``, ``init_state``,
  ``snip_mask``, ``fused_block_dispatch``, ...), since jax compiles
  lazily inside the first dispatch. Each duration also lands in the
  active tracer's span tree (``obs/trace.py``) as an event
  ``compile/trace`` / ``compile/lower`` / ``compile/backend``, a child
  of that same open span, ending when the listener fired: traces nest
  (a jitted function traced inside another's trace), so over a phase
  their union is the time, not their sum. Compilation-cache events
  (``/jax/compilation_cache/...``) land as counters, so persistent-
  cache hit rates are observable per run.
* :func:`jit_cost_analysis` — explicit AOT ``lower()``/``compile()``
  timing plus the lowered computation's ``cost_analysis()`` FLOPs /
  bytes-accessed where the backend reports them, for callers that want
  exact attribution of one entry point (tests, benches).

The watch is owned by ``ObsSession`` (install at session start,
uninstall on close), so obs-off runs never register a listener — the
monitoring hot path stays untouched, preserving the bit-identity and
overhead contracts ``scripts/obs_smoke.py`` enforces.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Dict, Optional

from . import metrics as obs_metrics, trace as obs_trace

logger = logging.getLogger(__name__)

__all__ = ["CompileWatch", "jit_cost_analysis"]

#: jax.monitoring duration events -> short registry metric names
#: (one distribution per compile phase: trace -> jaxpr, lower -> MLIR,
#: backend -> XLA compile proper)
COMPILE_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile_lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_backend_s",
}

#: compilation-cache occurrence events -> counter names
COMPILE_CACHE_EVENTS_PREFIX = "/jax/compilation_cache/"


def _cache_counter_name(event: str) -> str:
    # "/jax/compilation_cache/cache_hits" -> "compile_cache_cache_hits"
    return "compile_cache_" + event[len(COMPILE_CACHE_EVENTS_PREFIX):]


class CompileWatch:
    """Registers jax.monitoring listeners that feed ``registry``.

    ``install``/``uninstall`` are idempotent. Uninstall uses jax's
    private per-callback deregistration; if that API is ever absent the
    listeners stay registered but inert (the ``_live`` flag short-
    circuits them), so a closed session never keeps recording.
    """

    def __init__(self, registry: "obs_metrics.MetricsRegistry"):
        self._registry = registry
        self._live = False
        self._installed = False

    # listeners are bound methods so per-callback deregistration works
    def _on_duration(self, event: str, duration_secs: float,
                     **kwargs: Any) -> None:
        if not self._live:
            return
        name = COMPILE_DURATION_EVENTS.get(event)
        if name is None:
            return
        try:
            now = time.perf_counter_ns()
            entry = obs_trace.current_span_name() or "untraced"
            d = self._registry.distribution(name)
            d.observe(duration_secs)
            d.labels(entry=entry).observe(duration_secs)
            self._registry.counter("compile_events_total").inc()
            # the same duration in the span tree, under the span that
            # labels it: "compile_trace_s" -> "compile/trace"
            dur_ns = int(duration_secs * 1e9)
            obs_trace.get_tracer().record(
                "compile/" + name[len("compile_"):-len("_s")],
                now - dur_ns, dur_ns)
        except Exception:
            # jax.monitoring invokes listeners UNGUARDED inside the
            # compile path — any escape here (a label-cardinality
            # explosion, a foreign tracer without current_span_name)
            # would abort the compilation. Telemetry never kills the
            # run: log and drop.
            logger.debug("compile-event recording failed", exc_info=True)

    def _on_event(self, event: str, **kwargs: Any) -> None:
        if not self._live:
            return
        try:
            if event.startswith(COMPILE_CACHE_EVENTS_PREFIX):
                # labeled by entry point like the durations above, so a
                # run can say WHICH program hit the persistent cache
                c = self._registry.counter(_cache_counter_name(event))
                c.inc()
                c.labels(entry=obs_trace.current_span_name()
                         or "untraced").inc()
        except Exception:  # same unguarded-listener rule as above
            logger.debug("cache-event recording failed", exc_info=True)

    def install(self) -> "CompileWatch":
        if not self._installed:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
            self._installed = True
        self._live = True
        return self

    def uninstall(self) -> None:
        self._live = False
        if not self._installed:
            return
        try:
            from jax._src import monitoring as _m

            _m._unregister_event_duration_listener_by_callback(
                self._on_duration)
            _m._unregister_event_listener_by_callback(self._on_event)
            self._installed = False
        except Exception:  # pragma: no cover - private API drift
            # listeners stay registered but _live gates them off
            logger.debug("compile-watch deregistration unavailable",
                         exc_info=True)

    def summarize(self) -> Dict[str, float]:
        """Fold the per-phase distributions into end-of-run gauges
        (``compile_total_s``, ``compile_count``) so the one-glance
        metrics.json view does not require summing distributions."""
        total = 0.0
        count = 0
        for name in COMPILE_DURATION_EVENTS.values():
            if name in self._registry:
                d = self._registry.distribution(name)
                total += d.sum
                count = max(count, d.count)
        self._registry.gauge("compile_total_s").set(total)
        self._registry.gauge("compile_count").set(float(count))
        return {"compile_total_s": total, "compile_count": float(count)}


def jit_cost_analysis(fn, *args, registry=None, entry: str = "",
                      **kwargs) -> Dict[str, Any]:
    """AOT-measure one jitted callable on concrete args.

    Returns ``{compile_s, flops, bytes_accessed}`` — ``flops`` /
    ``bytes_accessed`` are None where the backend's ``cost_analysis()``
    does not report them (cost analysis is best-effort per backend).
    With ``registry`` + ``entry`` set, the numbers also land as labeled
    gauges (``compile_aot_s`` / ``compile_aot_flops`` /
    ``compile_aot_bytes``).
    """
    lowered = fn.lower(*args, **kwargs)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if ca.get("flops") is not None:
                flops = float(ca["flops"])
            if ca.get("bytes accessed") is not None:
                bytes_accessed = float(ca["bytes accessed"])
    except Exception:  # backend without cost analysis
        logger.debug("cost_analysis unavailable", exc_info=True)
    out = {"compile_s": compile_s, "flops": flops,
           "bytes_accessed": bytes_accessed}
    if registry is not None and entry:
        registry.gauge("compile_aot_s").labels(entry=entry).set(compile_s)
        if flops is not None:
            registry.gauge("compile_aot_flops").labels(
                entry=entry).set(flops)
        if bytes_accessed is not None:
            registry.gauge("compile_aot_bytes").labels(
                entry=entry).set(bytes_accessed)
    return out
