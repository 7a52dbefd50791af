"""The gated delta rule's chunked scan (ops/delta_rule.py): one meaning,
two lowerings.

* the Pallas kernels, interpreted (the spelling one TPU runs), against XLA's
  spelling (``decoder._delta_rule_chunks``, a ``lax.scan`` over steps of
  chunks): ``o``, ``keep``,
  the saved states and the gradients by ``q, k, v, g, beta`` of a random
  cotangent, at the cell's widths (128) and chunk (64) over a few hundred
  tokens: a sequence that whole steps do not divide, nearly alike keys,
  strong decay and none, two value heads on one key head, the compute type
  bfloat16; and against the token-by-token recurrence;
* what selects the lowering: the target, the widths and the chunk, each pass
  counted in ``linear_scan_lowerings``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as ref
from neuroimagedisttraining_tpu.models import decoder
from neuroimagedisttraining_tpu.ops import delta_rule as dr
from neuroimagedisttraining_tpu.ops import lowering

CHUNK = 64
COUNTER = "linear_scan_lowerings"


@pytest.fixture
def kernels_interpreted():
    """The TPU's lowering rule, interpreted, in the CPU's place."""
    with lowering.interpreted(dr._forward_p, dr._backward_p):
        yield


def counted(spelling, passes=("forward", "backward")):
    return {f"pass={p},spelling={spelling}": 1.0 for p in passes}


def operands(seq, key_heads, value_heads, *, alike=False, decay=(0.0, 0.05),
             dtype=jnp.float32, seed=0, width=128):
    """q, k L2-normed (q scaled) at the key heads, v, g uniform in
    ``-decay``, beta; ``alike``: keys of silu outputs shifted by 2 (mean
    ``|k . k|`` about 0.8, where powers of the chunk's matrix lose every
    digit)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = ref.l2_norm(jax.random.normal(keys[0], (1, seq, key_heads, width))
                    ) / np.sqrt(width)
    k = jax.random.normal(keys[1], (1, seq, key_heads, width))
    k = ref.l2_norm(jax.nn.silu(k) + 2.0 if alike else k)
    v = jax.random.normal(keys[2], (1, seq, value_heads, width))
    g = -jax.random.uniform(keys[3], (1, seq, value_heads),
                            minval=decay[0], maxval=decay[1])
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, seq, value_heads)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)


def relative(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


CASES = {
    # (seq, key heads, value heads, span, operands' options, tolerance of
    # the output and of the gradients)
    "steps_that_do_not_divide": (300, 2, 2, 2, {}, (2e-6, 2e-5)),
    "keys_alike": (192, 2, 2, 16, {"alike": True}, (2e-6, 2e-5)),
    "strong_decay": (192, 2, 2, 3, {"decay": (1.0, 6.0)}, (2e-6, 2e-5)),
    "no_decay": (192, 2, 2, 2, {"decay": (0.0, 0.0)}, (2e-6, 2e-5)),
    "two_value_heads_a_key_head": (256, 2, 4, 2, {}, (2e-6, 2e-5)),
    "bfloat16": (256, 1, 2, 2, {"dtype": jnp.bfloat16}, (1e-2, 2e-2)),
}


def with_gradients(scan):
    """``(o, keep, gradients of <o, w> by every operand)``, jitted afresh:
    each call lowers the program again under the rules in force."""
    def run(ops, w):
        (o, keep), pull = jax.vjp(scan, *ops)
        return o, keep, pull((w.astype(o.dtype), jnp.zeros_like(keep)))
    return jax.jit(run)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_xlas_spelling(case, kernels_interpreted, lowerings):
    """``decoder.delta_rule_scan`` on the interpreted kernels against XLA's
    spelling on the same operands: the output, ``keep`` (computed beside
    the op from ``g``), the state entering each step, and every operand's
    gradient of ``<o, w>``."""
    seq, nk, nv, span, options, (tol, grad_tol) = CASES[case]
    ops = operands(seq, nk, nv, **options)
    weights = jax.random.normal(jax.random.PRNGKey(7), (1, seq, nv, 128))

    def xla(*t):
        o, states = decoder._delta_rule_chunks(*t, CHUNK, span)
        return o, jnp.zeros(states.shape[:3])

    o, keep, got = with_gradients(
        lambda *t: decoder.delta_rule_scan(*t, CHUNK, span))(ops, weights)
    assert lowerings() == counted("kernel")
    want, _, expected = with_gradients(xla)(ops, weights)
    assert o.dtype == ops[2].dtype and o.shape == want.shape
    assert relative(o, want) < tol, relative(o, want)
    nc = -(-seq // CHUNK)
    g = jnp.pad(ops[3], [(0, 0), (0, nc * CHUNK - seq), (0, 0)])
    np.testing.assert_allclose(
        keep, np.exp(np.asarray(g).reshape(1, nc, CHUNK, nv).sum(2)),
        rtol=1e-5)
    for name, a, b in zip("q k v g beta".split(), got, expected):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert float(jnp.linalg.norm(b.astype(jnp.float32))) > 0, name
        assert relative(a, b) < grad_tol, (name, relative(a, b))
    states = jax.jit(lambda *t: dr._forward_p.bind(
        *t, body=decoder._delta_rule_chunks, chunk=CHUNK, span=span)[1])(
            *ops)
    assert relative(states, decoder._delta_rule_chunks(*ops, CHUNK, span)[1]
                    ) < max(tol, 1e-5)


def test_kernels_are_the_token_by_token_recurrence(kernels_interpreted,
                                                   lowerings):
    """Forward and every gradient against the recurrence itself (the plain
    reference's), two value heads on each key head, nearly alike keys and
    decays near 1, so that states cross every chunk."""
    ops = operands(200, 1, 2, alike=True, decay=(0.0, 0.02))
    weights = jax.random.normal(jax.random.PRNGKey(8), ops[2].shape)

    def plain(q, k, v, g, beta):
        q, k = (jnp.repeat(t, 2, axis=2) for t in (q, k))
        o = ref.delta_recurrence(q[0], k[0], v[0], g[0], beta[0])[None]
        return o, jnp.zeros((1, 4, 2))

    o, _, got = with_gradients(
        lambda *t: decoder.delta_rule_scan(*t, CHUNK, 2))(ops, weights)
    assert lowerings() == counted("kernel")
    want, _, expected = with_gradients(plain)(ops, weights)
    assert relative(o, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), got, expected):
        assert relative(a, b) < 5e-5, (name, relative(a, b))


def scan_lowered(platform, width, chunk, seq=128):
    q, k, v, g, beta = (jax.ShapeDtypeStruct(t.shape, t.dtype) for t in
                        operands(seq, 2, 4, width=width, dtype=jnp.bfloat16))

    def loss(q, k, v, g, beta):
        o = decoder.delta_rule_scan(q, k, v, g, beta, chunk)[0]
        return jnp.sum(o.astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=range(5))).trace(
        q, k, v, g, beta).lower(lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("platform,width,chunk,spelling", [
    ("tpu", 128, 64, "kernel"), ("tpu", 128, 8, "kernel"),
    ("tpu", 64, 64, "xla"), ("tpu", 128, 12, "xla"),
    ("cpu", 128, 64, "xla")])
def test_the_lowering_is_chosen_by_target_and_shape(platform, width, chunk,
                                                    spelling, lowerings):
    """Kernels where the target is a TPU, the widths are multiples of 128
    and the chunk of 8; XLA's scan anywhere else. Each pass is counted once
    under the spelling it took, and only the kernels' program holds Mosaic
    calls."""
    text = scan_lowered(platform, width, chunk)
    assert lowerings() == counted(spelling)
    assert ("tpu_custom_call" in text) == (spelling == "kernel")
    for name in ("delta_rule_forward", "delta_rule_backward"):
        assert (f"@{name}" in text) == (spelling == "kernel"), name


def test_the_tiny_preset_takes_xlas_spelling_on_the_cpu(lowerings):
    """The ``qwen3_next_tiny`` layer (widths 16, chunks of 8) trains on the
    CPU through XLA's spelling, both passes counted."""
    cfg = decoder.held_config("qwen3_next_tiny")
    layer = decoder.GatedDeltaNet(
        cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
        cfg["linear_conv_kernel_dim"], cfg["linear_chunk_size"],
        cfg["rms_norm_eps"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, cfg["hidden_size"]))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(3), x)["params"]
    params = jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 0.1, a.dtype), params)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply(
        {"params": p}, x))))(params)
    assert lowerings() == counted("xla")
    assert float(jnp.abs(grads["A_log"]).sum()) > 0


@pytest.mark.parametrize("value_heads,ratio,block", [
    (32, 2, 8), (4, 2, 4), (2, 1, 2), (16, 1, 8), (12, 3, 12), (24, 8, 8)])
def test_a_grid_step_holds_whole_groups_of_heads(value_heads, ratio, block):
    """A grid step's value heads: whole groups of a key head's, a whole
    tile of sublanes for the rows of ``g`` and ``beta`` (or every head), at
    most eight where such a block exists."""
    assert dr.head_block(value_heads, ratio) == block
