"""The round driver's host side, from the program's span tree
(``lib/spans.py``) and the profiler's trace of the traced block:

* ``host_busy_ms``: over the traced block, a ``round`` span's time less its
  ``flush`` children (the blocking fetch of the round before's record), per
  round: what the host itself needs to sample, dispatch and evaluate. Its
  inverse is the rate the host alone could sustain.
* ``block_max_over_median``: over the window's blocks (the depth-0 ``run``
  spans between set-up and the traced block), the longest over the median.
  ``rounds_per_s`` is taken from the median block, which hides a stall in
  fewer than half of them; the longest block's spans by name, beside the
  median block's, go to the details (``slow_block``).
* ``first_dispatch_ms``: in the profiler's trace, from the start of the host
  event ``run`` (the twin of the traced block's span) to the first device
  operation: the part before the window ``device_idle_share`` is taken over.

With the last, ``idle_by_span`` goes to the details: every idle gap of the
chip that idles most, summed by the innermost program span open when it
began (``NO_SPAN`` for none). The spans are the tracer's, laid on the
profiler's clock by the one twin: the tracer's times are ``perf_counter_ns``
from an origin with a unix time beside it (``to_unix_ns``), and the trace
counts from the ``profile_start_time`` of its ``Task Environment`` plane, which
this reduction does not hold; ``clock`` says what unix time the anchor puts
that zero at, and how far each other span then lies from its own twin.
A span the run did not record: nothing returned, listed in
``spans_missing``."""

import bisect
import statistics

from benchmarks.lib import spans as sp
from benchmarks.lib.reduce_trace import subtract, total

NO_SPAN = "(no program span)"
SPANS = ("run", "round", "flush")   # what this reader asks the tree for


def _window(ops) -> tuple:
    return min(o.start for o in ops), max(o.end for o in ops)


def _by_name_s(spans) -> dict:
    out = {}
    for s in spans:
        out[s.name] = out.get(s.name, 0.0) + s.seconds
    return out


def _host_busy_ms(ctx, tree):
    _, traced = sp.blocks(tree)
    rounds = [s for s in tree if traced is not None
              and s.parent == traced.id and s.name == "round"]
    if not rounds:
        sp.missing(ctx, "round")
        return None
    ids = {r.id for r in rounds}
    waits = sum(s.seconds for s in tree
                if s.parent in ids and s.name == "flush")
    return 1e3 * (sum(r.seconds for r in rounds) - waits) / len(rounds)


def _block_max_over_median(ctx, tree):
    window, _ = sp.blocks(tree)
    if not window:
        sp.missing(ctx, "run")
        return None
    seconds = [b.seconds for b in window]
    median = statistics.median(seconds)
    slow = max(window, key=lambda b: b.seconds)
    usual = min(window, key=lambda b: abs(b.seconds - median))
    ctx["details"]["slow_block"] = {
        "blocks": len(window), "index": window.index(slow),
        "seconds": slow.seconds, "median_s": median,
        "by_span_s": _by_name_s(sp.descendants(tree, slow)),
        "median_block_by_span_s": _by_name_s(sp.descendants(tree, usual))}
    return slow.seconds / median


def _twin(host, name, inside=None):
    """The host events called ``name`` (inside the event ``inside``), in
    order of start."""
    return sorted((h for h in host if h[0] == name and (
        inside is None or inside[1] <= h[1] and h[2] <= inside[2])),
        key=lambda h: h[1])


def _longest(events):
    return max(events, key=lambda h: h[2] - h[1]) if events else None


def _idle_by_span(ctx, tree, traced, twin):
    """Every idle gap of the chip that idles most, by program span."""
    tr = ctx["trace"]
    offset = twin[1] - traced.start     # tracer's clock -> the trace's
    program = [s for s in [traced] + sp.descendants(tree, traced)
               if not s.name.startswith(sp.COMPILE)]
    # the innermost span over each stretch between two span edges
    edges = sorted({t + offset for s in program for t in (s.start, s.end)})
    inner = []
    for a in edges[:-1]:
        open_ = [s for s in program if s.start + offset <= a < s.end + offset]
        inner.append(max(open_, key=lambda s: (s.depth, s.start)).name
                     if open_ else NO_SPAN)

    def idle(ops):
        w = _window(ops)
        return (w[1] - w[0]) - total((o.start, o.end) for o in ops)

    plane, ops = max(tr.devices.items(), key=lambda kv: idle(kv[1]))
    window = _window(ops)
    sums = {}
    for a, b in subtract([window], [(o.start, o.end) for o in ops]):
        i = bisect.bisect_right(edges, a) - 1
        name = inner[i] if 0 <= i < len(inner) else NO_SPAN
        sums[name] = sums.get(name, 0.0) + (b - a) / 1e9
    # how far the anchored spans lie from their own twins
    off_us = {}
    for name in sorted({s.name for s in program} - {"run"}):
        mine = [s for s in program if s.name == name]
        theirs = _twin(tr.host, name, twin)
        if len(mine) == len(theirs):
            off_us[name] = max(abs(h[1] - (s.start + offset)) / 1e3
                               for s, h in zip(mine, theirs))
    origin = getattr(sp.program_tracer(), "origin_unix_ns", None)
    ctx["details"]["idle_by_span"] = {
        "device": plane, "window_s": (window[1] - window[0]) / 1e9,
        "idle_s": idle(ops) / 1e9,
        "by_span_s": dict(sorted(sums.items(), key=lambda kv: -kv[1])),
        "clock": {"anchor": "the host event run",
                  "unix_ns_of_trace_zero": None if origin is None
                  else origin - round(offset),
                  "max_offset_from_twin_us": off_us}}


def _first_dispatch_ms(ctx, tree):
    _, traced = sp.blocks(tree)
    tr = ctx["trace"]
    twin = _longest(_twin(tr.host, "run",
                          _longest(_twin(tr.host, "traced_rounds"))))
    if traced is None or twin is None:
        sp.missing(ctx, "run")
        return None
    _idle_by_span(ctx, tree, traced, twin)
    first = min(_window(ops)[0] for ops in tr.devices.values())
    return (first - twin[1]) / 1e6


FIELDS = {"host_busy_ms": _host_busy_ms,
          "block_max_over_median": _block_max_over_median,
          "first_dispatch_ms": _first_dispatch_ms}


def read(ctx, field):
    if field not in FIELDS:
        raise ValueError(f"driver_host: unknown field {field!r}")
    return FIELDS[field](ctx, sp.tree_of(ctx))
