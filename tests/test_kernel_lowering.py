"""The rule every Pallas kernel primitive lowers by (ops/lowering.py), held
for each of them at a shape its own rule takes: the CPU's lowering takes
XLA's spelling and counts it; the TPU's rule, interpreted in the CPU's
place, takes the kernel (its jitted function) and counts it; lowered for a
TPU under a mesh of two devices that GSPMD partitions (a mesh outside any
``shard_map``) it declines, since GSPMD cannot partition a Mosaic kernel,
and counts XLA's spelling."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuroimagedisttraining_tpu.models import decoder
from neuroimagedisttraining_tpu.ops import (
    delta_rule,
    grouped_mlp,
    index_select,
    lowering,
    masked_attention,
    pool_vjp,
    selective_scan,
)

BF16, F32 = jnp.bfloat16, jnp.float32
Q, KV = (2, 1024, 1, 8, 128), (2, 1024, 1, 128)
ATTENTION = [(Q, BF16), (KV, BF16), (KV, BF16), ((2, 1024, 1024), jnp.int8)]
EXPERTS = [((512, 128), BF16), ((512,), F32)] + [((2, 128, 128), BF16)] * 3 \
    + [((4,), jnp.int32)]
LINEAR = [((2, 16, 1, 128), BF16)] * 3 + [((2, 16, 1), F32)] * 2
MAMBA = [((2, 16, 128), F32)] * 2 + [((128, 8), F32)] + [((2, 16, 8), F32)] * 2


def experts(backward):
    return {"pass=%s,product=%s" % (p, product)
            for p in ["backward" if backward else "forward"]
            for product in grouped_mlp.PRODUCTS[p]}


# primitive: (its operands, its parameters, counter, labels besides the
# spelling, the jitted kernel's name)
CASES = {
    "max_pool_first_match_scatter": (
        [((2, 9, 12, 9, 8), BF16)] + [((2, 5, 6, 5, 8), BF16)] * 2,
        dict(window=(3,) * 3, strides=(2,) * 3, padding=(1,) * 3,
             batch_dims=0), "pool_bwd_lowerings", {"geometry=3_2_1"},
        "pool_backward"),
    "masked_attention": (
        ATTENTION, dict(attend=decoder._attend, kind="selected", window=None),
        "attention_lowerings", {"kind=selected,pass=forward"},
        "attention_forward"),
    "masked_attention_backward": (
        ATTENTION + [(Q, BF16), ((2, 1, 8, 1, 1024), F32), (Q, BF16)],
        dict(attend=decoder._attend, kind="selected", window=None),
        "attention_lowerings", {"kind=selected,pass=backward"},
        "attention_backward"),
    "grouped_swiglu": (EXPERTS, dict(tm=128), "expert_lowerings",
                       experts(False), "experts_forward"),
    "grouped_swiglu_backward": (
        EXPERTS + [((512, 128), BF16)], dict(tm=128), "expert_lowerings",
        experts(True), "experts_backward"),
    "delta_rule": (
        LINEAR, dict(body=decoder._delta_rule_chunks, chunk=8, span=2),
        "linear_scan_lowerings", {"pass=forward"}, "delta_rule_forward"),
    "delta_rule_backward": (
        LINEAR + [((2, 1, 1, 128, 128), F32), ((2, 16, 1, 128), BF16)],
        dict(body=decoder._delta_rule_chunks, chunk=8, span=2),
        "linear_scan_lowerings", {"pass=backward"}, "delta_rule_backward"),
    "selective_scan": (
        MAMBA, dict(body=decoder._selective_scan_chunks, chunk=8),
        "mamba_scan_lowerings", {"pass=forward"}, "selective_scan_forward"),
    "selective_scan_backward": (
        MAMBA + [((2, 2, 8, 128), F32), ((2, 16, 128), F32)],
        dict(body=decoder._selective_scan_chunks, chunk=8),
        "mamba_scan_lowerings", {"pass=backward"},
        "selective_scan_backward"),
    "index_select": (
        [((2, 4096, 16, 64), BF16), ((2, 4096, 16), F32),
         ((2, 4096, 64), BF16)],
        dict(topk=2048, blocks=decoder._selection_blocks, block=1024),
        "selection_lowerings", {""}, "index_select"),
}
PRIMITIVES = {p.name: p for p in (
    pool_vjp._scatter_p, masked_attention._forward_p,
    masked_attention._backward_p, grouped_mlp._forward_p,
    grouped_mlp._backward_p, delta_rule._forward_p, delta_rule._backward_p,
    index_select._select_p, selective_scan._forward_p,
    selective_scan._backward_p)}


@pytest.mark.parametrize("where", ["cpu", "tpu_interpreted_on_the_cpu",
                                   "tpu_under_gspmd"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_rule_picks_the_spelling_and_counts_it(name, where, lowerings):
    operands, params, counter, labels, kernel = CASES[name]
    p = PRIMITIVES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in operands]
    shardings = None
    if where == "tpu_under_gspmd":
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("clients",))
        shardings = [NamedSharding(mesh, P("clients"))] + [
            NamedSharding(mesh, P())] * (len(args) - 1)
    traced = jax.jit(lambda *a: p.bind(*a, **params),
                     in_shardings=shardings).trace(*args)
    if where == "tpu_interpreted_on_the_cpu":
        with lowering.interpreted(p):
            text = traced.lower().as_text()
    else:
        text = traced.lower(lowering_platforms=(
            "tpu" if where == "tpu_under_gspmd" else "cpu",)).as_text()
    took = where == "tpu_interpreted_on_the_cpu"
    spelling = "kernel" if took else (
        "blocks" if name == "index_select" else "xla")
    assert lowerings(counter) == {
        ",".join(filter(None, (each, "spelling=" + spelling))): 1.0
        for each in labels}
    assert "tpu_custom_call" not in text
    assert bool(re.search(rf"call @{kernel}(_\d+)?\(", text)) == took
