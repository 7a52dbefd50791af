"""The seam between the Pallas kernel modules (``pool_vjp``,
``masked_attention``, ``grouped_mlp``, ``delta_rule``, ``index_select``,
``selective_scan``) and the compiler, written once.

Each of their primitives has one meaning and two spellings, and the lowering
picks one where the program is lowered (:func:`register`): the module's
kernels where the target is a TPU, no partitioner will touch the op (GSPMD
cannot partition a Mosaic kernel: :func:`unpartitioned`) and the module's
own rule of shapes and dtypes takes the operands; XLA's spelling everywhere
else (the CPU, shapes the kernels do not take, whatever GSPMD partitions).
The choice is made once per lowering, so lowerings are what is counted, in
the module's counter of ``obs/metrics.get_registry()``. Of the kernel
modules only this one reaches into JAX's internals or imports Pallas.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax._src import dispatch, sharding_impls
from jax.extend.core import Primitive
from jax.interpreters import mlir

LANES = 128
NT = (((1,), (1,)), ((), ()))       # a . b^T
TN = (((0,), (0,)), ((), ()))       # a^T . b

# each primitive's TPU rule, which :func:`interpreted` puts in the CPU's place
_TPU_RULES = {}


def vma(*avals):
    """The manual mesh axes of an enclosing ``shard_map`` over which any of
    ``avals`` varies: the result varies over them as the operands do."""
    return frozenset().union(*(a.vma for a in avals))


def unpartitioned(context) -> bool:
    """Whether an op lowered under ``context`` reaches the compiler as it is:
    the program is one device's, or every mesh axis of more than one device
    is manual (the inside of a ``shard_map`` over all of them)."""
    if isinstance(context, sharding_impls.ShardingContext):
        return context.num_devices == 1
    if isinstance(context, sharding_impls.SPMDAxisContext):
        return all(size == 1 or name in context.manual_axes
                   for name, size in context.mesh.shape.items())
    return False


def primitive(name, abstract_eval, multiple_results=True):
    p = Primitive(name)
    p.multiple_results = multiple_results
    p.def_impl(functools.partial(dispatch.apply_primitive, p))
    p.def_abstract_eval(abstract_eval)
    return p


def count(counter: str, **labels) -> None:
    from ..obs.metrics import get_registry
    get_registry().counter(counter).labels(**labels).inc()


def register(*passes, rule, kernel, xla, counter, labels=lambda **_: [{}],
             xla_spelling="xla"):
    """Lowers a primitive, or a forward and a backward one (``backward``
    among their params, ``pass`` among their labels), on a TPU to
    ``kernel(interpret=..., **params)`` where ``rule(avals_in, **params)``
    takes the operands and no partitioner touches the op, else to
    ``xla(**params)``. A lowering counts once in ``counter`` a label set of
    ``labels(**params)``, with ``spelling`` ``kernel`` or ``xla_spelling``."""
    def lower(ctx, *args, p, fixed, named, kernels, interpret=False,
              **params):
        params.update(fixed)
        take = bool(kernels
                    and unpartitioned(ctx.module_context.axis_context)
                    and rule(ctx.avals_in, **params))
        for each in labels(**params):
            count(counter, spelling="kernel" if take else xla_spelling,
                  **each, **named)
        fn = kernel(interpret=interpret, **params) if take else xla(**params)
        return mlir.lower_fun(fn, multiple_results=p.multiple_results)(
            ctx, *args)

    pair = len(passes) == 2
    for i, p in enumerate(passes):
        rule_of = functools.partial(
            lower, p=p, fixed={"backward": bool(i)} if pair else {},
            named={"pass": ("forward", "backward")[i]} if pair else {})
        _TPU_RULES[p] = functools.partial(rule_of, kernels=True)
        mlir.register_lowering(p, functools.partial(rule_of, kernels=False))
        mlir.register_lowering(p, _TPU_RULES[p], platform="tpu")


@contextlib.contextmanager
def interpreted(*primitives):
    """The TPU's rule for ``primitives``, the kernels interpreted, in the
    CPU's place, and the CPU's own again on exit; the kernels are jitted
    afresh on both sides (module constants patched between are traced)."""
    jitted.cache_clear()
    for p in primitives:
        mlir.register_lowering(p, functools.partial(
            _TPU_RULES[p], interpret=True), platform="cpu")
    try:
        yield
    finally:
        jitted.cache_clear()
        for p in primitives:
            del mlir._platform_specific_lowerings["cpu"][p]


def pallas():
    """``(pl, pltpu)``, imported here: a second and a half that only a
    process which lowers a kernel should pay (no CPU run does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


@functools.lru_cache(maxsize=None)
def jitted(name: str, fn, **statics):
    """``fn`` with ``statics`` bound, jitted under the function name that
    every op of the kernel carries in a trace: every program of a process
    that holds it at the same shapes traces its body once."""
    call = functools.partial(fn, **statics)
    call.__name__ = name
    return jax.jit(call)


def wide(col, width):
    """A per-row value kept on all 128 lanes, as wide as a tile."""
    return jnp.tile(col, (1, width // LANES))


def as_col(row, rows):
    """``row [1, rows]`` as ``[rows, 128]``, the value on every lane."""
    return jnp.broadcast_to(row, (LANES, rows)).T
