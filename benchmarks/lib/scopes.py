"""Which ``jax.named_scope`` and which pass an HLO instruction belongs to,
read from its ``op_name`` metadata.

An ``op_name`` is the JAX name stack at the place the operation was traced,
joined by ``/``::

    jit(round_fn)/local_train/while/body/closed_call/vmap()/while/body/
      closed_call/transpose(jvp(AlexNet3DS2D))/S2DStemStage_0/stem/conv/mul

* A transform wraps the one segment that follows it: ``jit(round_fn)``,
  ``vmap(batch_gather)``, ``jvp(AlexNet3DS2D)``, ``transpose(jvp(X))``,
  ``vmap()`` where nothing follows. So the pass is in the name: under a
  ``transpose`` an instruction is backward, under a ``jvp`` alone forward.
* XLA joins the names of instructions it merged with ``;``; the later parts
  come without the prefix. An instruction matches if any part does.
* Instructions XLA made itself may carry only the tail of the path
  (``transpose(jvp(X))/GroupNorm_0/reduce_sum``), a bare primitive
  (``reduce_sum``, ``scatter``) or nothing, and an inner jitted helper keeps
  the name stack of whoever traced it first, so a Python function's
  qualified name in a path proves nothing. Hence a scope is matched as whole
  segments **anywhere** in the path, never as a prefix, and only names the
  program sets with ``jax.named_scope`` (or flax gives a module) are asked for.

The compile cache's key leaves this metadata out: after an edit to a scope,
an executable loaded from the cache still carries the old names. Empty the
cache directory before a traced run that follows such an edit.
"""
from __future__ import annotations

import functools
import re

WRAPPED = re.compile(r"(\w+)\((.*)\)\Z")

STALE_CACHE = (
    "no instruction of the traced round is under this scope although "
    "local_train is there: either the program has no such scope, or the "
    "round's executable came from a persistent compile cache filled before "
    "the scope was added or renamed (the cache key leaves op_name metadata "
    "out): empty the compile cache directory and run again")


@functools.lru_cache(maxsize=1 << 16)
def parse(op_name: str) -> tuple:
    """``((segments, transforms), ...)``, one pair per ``;``-joined part:
    the path's plain segments with every transform unwrapped (``vmap()``
    leaves none), and per segment the tuple of transforms that wrapped it,
    outermost first."""
    parts = []
    for part in op_name.split(";"):
        segments, wraps = [], []
        for seg in part.split("/"):
            around = []
            while (m := WRAPPED.match(seg)):
                around.append(m.group(1))
                seg = m.group(2)
            if seg or around:
                segments.append(seg)
                wraps.append(tuple(around))
        parts.append((tuple(segments), tuple(wraps)))
    return tuple(parts)


def transforms(op_name: str) -> frozenset:
    """Every transform that wraps a segment of any part of ``op_name``."""
    return frozenset(t for _, wraps in parse(op_name)
                     for around in wraps for t in around)


@functools.lru_cache(maxsize=1 << 16)
def direction(op_name: str):
    """``"bwd"`` under a ``transpose``, ``"fwd"`` under a ``jvp`` and no
    ``transpose``, else ``None`` (the work is no part of a gradient)."""
    found = transforms(op_name)
    if "transpose" in found:
        return "bwd"
    return "fwd" if "jvp" in found else None


@functools.lru_cache(maxsize=1 << 18)    # op_names x scopes asked for
def under(op_name: str, scope: str) -> bool:
    """Whether the segments of ``scope`` (``"stem/conv"``) appear next to
    each other anywhere in a part of ``op_name``."""
    want = tuple(scope.split("/"))
    n = len(want)
    return any(segments[i:i + n] == want
               for segments, _ in parse(op_name)
               for i in range(len(segments) - n + 1))


def matcher(scope=None, want_direction=None, program=""):
    """A predicate over trace ops: of a program whose name starts with
    ``program``, under ``scope`` (if given) and of pass ``want_direction``
    (if given). The harness joins ``op_name``s from the round program's HLO
    by instruction name, so on another program's ops they are chance."""
    def keep(op) -> bool:
        return (op.program.startswith(program)
                and (scope is None or under(op.op_name, scope))
                and (want_direction is None
                     or direction(op.op_name) == want_direction))
    return keep


def inherited(ops):
    """``(op, op_name)`` for the ops of one device as ``reduce_trace.nest``
    left them (sorted, enclosing op first): an op XLA gave no ``op_name`` at
    all takes the name of the op it is nested in, the loop or call that runs
    it (XLA expands a gather of whole volumes into a ``while`` that keeps the
    gather's name over a body of nameless slice fusions)."""
    stack = []
    for op in ops:
        while stack and (stack[-1][0].end <= op.start
                         or stack[-1][0].end < op.end):
            stack.pop()
        name = op.op_name or (stack[-1][1] if stack else "")
        stack.append((op, name))
        yield op, name


def note_missing(ctx, scope: str, program="") -> None:
    """Record in the run's details that ``scope`` matched nothing although
    the program was traced with its names (``local_train`` matched)."""
    if ctx["trace"].where_s(matcher("local_train", program=program)):
        ctx["details"].setdefault("scopes_missing", {})[scope] = STALE_CACHE
