"""The program's span tree, as the span readers walk it.

The program's tracer (``obs/trace.py``) keeps one tree per process: every
event has a ``span_id``, the ``parent`` that was open when it began, a depth,
and at the exit of a depth-0 span the allocator's bytes. The harness wraps
set-up's five calls in spans of that tracer (``harness.SETUP_SPANS``) and the
program records its own inside them (the list: ``obs/trace.py``'s docstring),
so one walk accounts for ``setup_s`` and for the watermark by phase:

* set-up ends at the end of the last depth-0 span named in ``SETUP_SPANS``;
* the window's blocks are the depth-0 ``run`` spans after it, and the traced
  block is the last of them (a reader runs in a traced run only);
* a span's self time is what no deeper event covers. Recorded compile
  durations nest in time under one parent (a function traced inside
  another's trace), so self times come from a sweep in which every instant
  belongs to the deepest, latest-begun event that covers it: they add up to
  the union of the depth-0 spans exactly.

Everything here is arithmetic on a list of events; a tracer without the tree
(the parent commit's) gives an empty list, and every reader then returns
nothing.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field

from . import harness
from .reduce_trace import total

COMPILE = "compile/"


@dataclass
class Span:
    id: int
    parent: int | None
    name: str
    start: float        # ns on the tracer's clock (its origin is 0)
    end: float
    depth: int
    args: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


def program_tracer():
    """The tracer the program has installed, or None where there is no
    program beside the benchmark."""
    try:
        from neuroimagedisttraining_tpu.obs import trace
    except ImportError:
        return None
    return trace.get_tracer()


def spans_of(events) -> list:
    """The events that are part of a tree, in order of their start."""
    out = []
    for e in events or ():
        if "span_id" not in e:
            continue
        args = e.get("args") or {}
        start = e["ts"] * 1e3
        out.append(Span(e["span_id"], e.get("parent"), e["name"], start,
                        start + e["dur"] * 1e3, args.get("depth", 0), args))
    out.sort(key=lambda s: (s.start, s.id))
    return out


def setup_end(spans):
    """Where set-up ends on the tracer's clock, or None."""
    ends = [s.end for s in spans
            if s.depth == 0 and s.name in harness.SETUP_SPANS]
    return max(ends) if ends else None


def setup_roots(spans) -> list:
    """Set-up's depth-0 spans, in order."""
    end = setup_end(spans)
    return [s for s in spans if s.depth == 0 and end is not None
            and s.end <= end]


def blocks(spans) -> tuple:
    """``(window, traced)``: the depth-0 ``run`` spans after set-up but the
    last, and the last (None where there is none)."""
    end = setup_end(spans)
    runs = [s for s in spans if s.depth == 0 and s.name == "run"
            and end is not None and s.start >= end]
    return (runs[:-1], runs[-1]) if runs else ([], None)


def descendants(spans, root: Span) -> list:
    """Every span under ``root``, in order of start."""
    under, out = {root.id}, []
    for s in sorted(spans, key=lambda s: s.id):   # a parent opens first
        if s.parent in under:
            under.add(s.id)
            out.append(s)
    return sorted(out, key=lambda s: (s.start, s.id))


def paths(spans) -> dict:
    """``span id -> "init_state/snip_mask/compile/backend"``."""
    by_id = {s.id: s for s in spans}
    out = {}

    def path(s):
        if s.id not in out:
            up = by_id.get(s.parent)
            out[s.id] = (path(up) + "/" if up else "") + s.name
        return out[s.id]

    for s in spans:
        path(s)
    return out


def self_ns(spans) -> dict:
    """``span id -> ns`` in which that span was the deepest, latest-begun
    one open. Over the spans under some roots the values add up to the
    union of the roots."""
    edges = sorted({t for s in spans for t in (s.start, s.end)})
    starting = sorted(spans, key=lambda s: s.start)
    out, heap, i = {s.id: 0.0 for s in spans}, [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(starting) and starting[i].start <= a:
            s = starting[i]
            heapq.heappush(heap, (-s.depth, -s.start, -s.id, s.end))
            i += 1
        while heap and heap[0][3] <= a:
            heapq.heappop(heap)
        if heap:
            out[-heap[0][2]] += b - a
    return out


def union_s(spans) -> float:
    return total((s.start, s.end) for s in spans) / 1e9


def missing(ctx, name: str) -> None:
    """Note in the run's details that no span ``name`` was recorded."""
    listed = ctx["details"].setdefault("spans_missing", [])
    if name not in listed:
        listed.append(name)


def tree_of(ctx) -> list:
    """The installed tracer's tree, built once a run; the details get their
    ``spans_missing`` list, empty so far, so that a run's file says the
    readers looked."""
    ctx["details"].setdefault("spans_missing", [])
    if "span_tree" not in ctx:
        ctx["span_tree"] = spans_of(getattr(program_tracer(), "events", None))
    return ctx["span_tree"]
