"""From a profiler trace of a few steady rounds to per-layer numbers.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it with nothing but JAX. A TPU plane (``/device:TPU:n``) has a line of XLA
modules (one event per program run) and a line of XLA ops (one event per HLO
instruction run, nested where an instruction is a loop or a call). Everything
below is arithmetic on intervals in nanoseconds (copied in spirit from
``obs/devtrace.py``, which reduces only collective-vs-compute overlap):

* busy time is the union of the op intervals of a device, the window runs
  from its first op to its last, and the idle gaps are what is left;
* an op belongs to the program whose module event contains it, and to the
  ``jax.named_scope`` its ``op_name`` lies under; the events carry the HLO
  instruction and no ``op_name``, so that is joined by instruction name from
  the text of the compiled module;
* an op's self time is its interval less the ops nested in it, so a loop is
  not counted on top of its body;
* a collective is exposed while no other leaf op runs on that device.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
# event stats that carry the JAX op_name path, in order of preference
NAMED_GAPS = 100
# a TPU op event is named by its whole HLO line: "%fusion.5 = bf16[..] fusion(..."
INSTRUCTION = re.compile(r"%?([\w.\-]+)")


@dataclass
class Op:
    name: str       # HLO instruction name ("fusion.5")
    start: float    # ns
    end: float
    program: str = ""    # XLA module, without its run id
    op_name: str = ""    # "jit(round_fn)/.../local_train/..."
    self_ns: float = 0.0
    leaf: bool = True

    @property
    def label(self) -> str:
        """Program, instruction and the tail of its op_name (the model's
        layer and the JAX primitive), for the breakdown."""
        tail = "/".join(self.op_name.split("/")[-2:])
        return f"{self.program}/{self.name}" + (f" [{tail}]" if tail else "")


def union(intervals) -> list:
    """Disjoint sorted union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> list:
    """The parts of the union of ``a`` that no interval of ``b`` covers."""
    out, b = [], union(b)
    for s, e in union(a):
        for bs, be in b:
            if be <= s:
                continue
            if bs >= e:
                break
            if bs > s:
                out.append([s, bs])
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append([s, e])
    return out


def nest(ops) -> None:
    """Fill ``self_ns`` and ``leaf`` of the ops of one device: an op that
    runs wholly inside another is nested in it."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack = []
    for op in ops:
        op.self_ns, op.leaf = op.end - op.start, True
        while stack and (stack[-1].end <= op.start or stack[-1].end < op.end):
            stack.pop()
        if stack:
            stack[-1].self_ns -= op.end - op.start
            stack[-1].leaf = False
        stack.append(op)


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def in_scope(op: Op, scope: str) -> bool:
    return f"/{scope}/" in f"/{op.op_name}/"


def hlo_op_names(hlo_text) -> dict:
    """``instruction name -> op_name`` from the text of a compiled module
    (``compiled.as_text()``)."""
    if not hlo_text:
        return {}
    pattern = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"", re.M)
    return dict(pattern.findall(hlo_text))


class Trace:
    """The ops of each device over the traced rounds, and the host's spans.
    Times of several devices are averaged, except where a method says it
    takes the worst."""

    def __init__(self, devices: dict, host: list, rounds: int):
        self.devices = devices      # plane name -> [Op], nested
        self.host = host            # [(name, start, end)]
        self.rounds = rounds
        if not any(devices.values()):
            raise ValueError("the trace holds no device operation")

    def _mean(self, fn) -> float:
        return sum(fn(ops) for ops in self.devices.values()) / len(
            self.devices)

    @staticmethod
    def _window(ops):
        return min(o.start for o in ops), max(o.end for o in ops)

    @property
    def busy_s(self) -> float:
        return self._mean(
            lambda ops: total((o.start, o.end) for o in ops)) / 1e9

    @property
    def window_s(self) -> float:
        return self._mean(lambda ops: self._window(ops)[1]
                          - self._window(ops)[0]) / 1e9

    def idle_share(self) -> float:
        """1 - busy / window on the device that idles most."""
        def idle(ops):
            w0, w1 = self._window(ops)
            return 1.0 - total((o.start, o.end) for o in ops) / (w1 - w0)
        return max(idle(ops) for ops in self.devices.values())

    def where_s(self, keep) -> float:
        """Seconds in which an op chosen by ``keep`` ran (their union)."""
        return self._mean(lambda ops: total(
            (o.start, o.end) for o in ops if keep(o))) / 1e9

    def exposed_collective_s(self) -> float:
        """Seconds of collective ops during which no other leaf op ran on
        the same device."""
        def exposed(ops):
            coll = [(o.start, o.end) for o in ops
                    if o.leaf and is_collective(o.name)]
            rest = [(o.start, o.end) for o in ops
                    if o.leaf and not is_collective(o.name)]
            return sum(e - s for s, e in subtract(coll, rest))
        return self._mean(exposed) / 1e9

    def top_ops(self, n: int) -> list:
        """``[label, seconds]`` of the ops with most self time, summed over
        the traced rounds and averaged over the devices."""
        sums = {}
        for ops in self.devices.values():
            for o in ops:
                sums[o.label] = sums.get(o.label, 0.0) + o.self_ns
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / len(self.devices)] for k, v in ranked]

    def top_gaps(self, n: int) -> list:
        """``[host span, seconds]``: the idle gaps of the device that idles
        most, each named by the innermost host span open when it began, and
        summed by that name. Only the :data:`NAMED_GAPS` longest gaps are
        looked up; the rest go under one name."""
        ops = max(self.devices.values(), key=lambda ops: total(
            [self._window(ops)]) - total((o.start, o.end) for o in ops))
        gaps = sorted(subtract([self._window(ops)],
                               [(o.start, o.end) for o in ops]),
                      key=lambda g: g[0] - g[1])
        sums = {}
        if gaps[NAMED_GAPS:]:
            sums["(shorter gaps)"] = sum(e - s for s, e in gaps[NAMED_GAPS:])
        for s, e in gaps[:NAMED_GAPS]:
            open_ = [h for h in self.host if h[1] <= s < h[2]]
            name = min(open_, key=lambda h: h[2] - h[1])[0] if open_ \
                else "(no host span)"
            sums[name] = sums.get(name, 0.0) + (e - s)
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in ranked]


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str, devices: int, rounds: int,
         op_names: dict = None) -> Trace:
    """The newest trace under ``trace_dir`` as a :class:`Trace` of the first
    ``devices`` TPU planes."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(newest_xplane(trace_dir))
    planes, host = {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.search(plane.name):
            planes[plane.name] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.duration_ns > 0]
    out = {}
    for plane_name in sorted(planes)[:devices]:
        lines = {line.name: line.events for line in planes[plane_name].lines}
        modules = [(re.sub(r"\(\d+\)$", "", e.name), e.start_ns,
                    e.start_ns + e.duration_ns)
                   for e in lines.get(MODULES_LINE, ())]
        ops = []
        for e in lines.get(OPS_LINE, ()):
            name = INSTRUCTION.match(e.name).group(1)
            program = next((m for m, s, t in modules
                            if s <= e.start_ns < t), "")
            ops.append(Op(name, e.start_ns, e.start_ns + e.duration_ns,
                          program, (op_names or {}).get(name, "")))
        nest(ops)
        out[plane_name] = ops
    return Trace(out, host, rounds)
