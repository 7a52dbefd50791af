"""Mamba-1's selective scan (ops/selective_scan.py): one meaning, two
lowerings.

* the Pallas kernels, interpreted (the spelling one TPU runs), against XLA's
  spelling (``decoder._selective_scan_chunks``, a ``lax.scan`` over chunks of
  a ``lax.scan`` over tokens): ``y``, ``keep``, the saved states and the
  gradients by ``u, delta, a, b, c, d_skip`` of a random cotangent, over two
  channel blocks of 128 lanes, states of 16 and 8, chunks of 64 and 8, a
  sequence the chunk does not divide, strong decay and none; and both
  spellings against the token-by-token recurrence;
* what selects the lowering: the target, the widths and the chunk, each pass
  counted in ``mamba_scan_lowerings``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4_flash as ref
from neuroimagedisttraining_tpu.models import decoder
from neuroimagedisttraining_tpu.ops import lowering
from neuroimagedisttraining_tpu.ops import selective_scan as ss

COUNTER = "mamba_scan_lowerings"


def counted(spelling, passes=("forward", "backward")):
    return {f"pass={p},spelling={spelling}": 1.0 for p in passes}


def operands(seq, channels=256, state=16, *, decay=(1e-3, 1e-1), a=None,
             seed=0):
    """``delta`` log-uniform in ``decay`` (Mamba's draw by default), ``a``
    S4D-real's ``-(1 .. state)`` unless given, ``u, b, c`` normal, ``d_skip``
    a ramp."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    u = jax.random.normal(keys[0], (1, seq, channels))
    delta = jnp.exp(jax.random.uniform(
        keys[1], (1, seq, channels), minval=np.log(decay[0]),
        maxval=np.log(decay[1])))
    if a is None:
        a = -jnp.broadcast_to(jnp.arange(1, state + 1, dtype=jnp.float32),
                              (channels, state))
    b = jax.random.normal(keys[2], (1, seq, state))
    c = jax.random.normal(keys[3], (1, seq, state))
    return u, delta, a, b, c, jnp.linspace(0.5, 1.5, channels)


def relative(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def with_gradients(scan):
    """``(y, keep, gradients of <y, w> by every operand)``, jitted afresh:
    each call lowers the program again under the rules in force."""
    def run(ops, w):
        (y, keep), pull = jax.vjp(scan, *ops)
        return y, keep, pull((w, jnp.zeros_like(keep)))
    return jax.jit(run)


CASES = {
    # (seq, state, chunk, channel blocks of 128 lanes or None for one block
    # of every channel, operands' options)
    "two_channel_blocks": (200, 16, 64, 2, {}),
    "state_8_chunk_8": (200, 8, 8, None, {}),
    "strong_decay": (136, 16, 8, None, {"decay": (0.5, 3.0)}),
    "no_decay": (136, 8, 64, 2, {"a": jnp.zeros((256, 8))}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_xlas_spelling(case, monkeypatch, lowerings):
    """``decoder.selective_scan`` on the interpreted kernels against XLA's
    spelling on the same operands: ``y``, ``keep`` (computed beside the op
    from ``delta``), the state entering each chunk, and every operand's
    gradient of ``<y, w>``."""
    seq, state, chunk, blocks, options = CASES[case]
    if blocks:      # a tile of ``chunk x 128`` at most: two channel blocks
        monkeypatch.setattr(ss, "_TILE", chunk * 128)
    ops = operands(seq, state=state, **options)
    weights = jax.random.normal(jax.random.PRNGKey(7), ops[0].shape)
    with lowering.interpreted(ss._forward_p, ss._backward_p):
        assert ss.channel_block(256, chunk) == (128 if blocks else 256)
        y, keep, got = with_gradients(
            lambda *t: decoder.selective_scan(*t, chunk))(ops, weights)
        assert lowerings() == counted("kernel")
        states = jax.jit(lambda *t: ss._forward_p.bind(
            *t, body=decoder._selective_scan_chunks, chunk=chunk)[1])(
                *ops[:5])
    want, want_keep, expected = with_gradients(
        lambda *t: decoder.selective_scan(*t, chunk))(ops, weights)
    assert y.dtype == jnp.float32 and y.shape == want.shape
    assert relative(y, want) < 1e-6, relative(y, want)
    np.testing.assert_allclose(keep, want_keep, rtol=1e-6)
    for name, g, e in zip("u delta a b c d_skip".split(), got, expected):
        assert g.dtype == e.dtype and g.shape == e.shape, name
        assert float(jnp.linalg.norm(e)) > 0, name
        assert relative(g, e) < 1e-5, (name, relative(g, e))
    assert states.shape == (1, -(-seq // chunk), state, 256)
    assert relative(states, decoder._selective_scan_chunks(
        *ops[:5], chunk)[1]) < 1e-6


@pytest.mark.parametrize("spelling", ["kernel", "xla"])
def test_both_spellings_are_the_token_by_token_recurrence(spelling,
                                                          lowerings):
    """Forward and every gradient against the plain reference's recurrence,
    on a draw whose states cross the chunks (chunks of 8 over 100 tokens,
    what a chunk keeps of its entering state well over 0.1)."""
    ops = operands(100, channels=128, state=8, decay=(1e-3, 2e-2))
    weights = jax.random.normal(jax.random.PRNGKey(8), ops[0].shape)

    def plain(u, delta, a, b, c, d):
        y = ref.recurrence(u[0], delta[0], a, b[0], c[0], d)[None]
        return y, jnp.zeros((1, 13))

    def chunked(*t):
        return decoder.selective_scan(*t, 8)

    if spelling == "kernel":
        with lowering.interpreted(ss._forward_p, ss._backward_p):
            y, keep, got = with_gradients(chunked)(ops, weights)
    else:
        y, keep, got = with_gradients(chunked)(ops, weights)
    assert lowerings() == counted(spelling)
    assert float(jnp.mean(keep)) > 0.1
    want, _, expected = with_gradients(plain)(ops, weights)
    assert relative(y, want) < 1e-5
    for name, g, e in zip("u delta a b c d_skip".split(), got, expected):
        assert relative(g, e) < 5e-5, (name, relative(g, e))


def scan_lowered(platform, channels, chunk, seq=64):
    u, delta, a, b, c, d = (jax.ShapeDtypeStruct(t.shape, t.dtype) for t in
                            operands(seq, channels=channels, state=16))

    def loss(u, delta, a, b, c, d):
        return jnp.sum(decoder.selective_scan(u, delta, a, b, c, d,
                                              chunk)[0])
    return jax.jit(jax.grad(loss, argnums=range(6))).trace(
        u, delta, a, b, c, d).lower(lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize("platform,channels,chunk,spelling", [
    ("tpu", 128, 64, "kernel"), ("tpu", 256, 8, "kernel"),
    ("tpu", 96, 64, "xla"), ("tpu", 128, 12, "xla"),
    ("cpu", 128, 64, "xla")])
def test_the_lowering_is_chosen_by_target_and_shape(platform, channels,
                                                    chunk, spelling,
                                                    lowerings):
    """Kernels where the target is a TPU, the channels are a multiple of
    128, the state of 8 and the chunk of 8; XLA's scan anywhere else. Each
    pass is counted once under the spelling it took, and only the kernels'
    program holds Mosaic calls."""
    text = scan_lowered(platform, channels, chunk)
    assert lowerings() == counted(spelling)
    assert ("tpu_custom_call" in text) == (spelling == "kernel")
    for name in ("selective_scan_forward", "selective_scan_backward"):
        assert (f"@{name}" in text) == (spelling == "kernel"), name


def test_the_tiny_preset_takes_xlas_spelling_on_the_cpu(lowerings):
    """The ``phi4_flash_tiny`` mixer (1024 channels, states of 8, chunks of
    8: widths the kernels take) trains on the CPU through XLA's spelling,
    both passes counted."""
    cfg = decoder.held_config("phi4_flash_tiny")
    layer = decoder.Mamba(
        cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
        cfg["mamba_d_conv"], cfg["mamba_dt_rank"], cfg["mamba_chunk_size"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 20, cfg["hidden_size"]))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(3), x)["params"]
    params = jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 0.1, a.dtype), params)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply(
        {"params": p}, x)[0])))(params)
    assert lowerings() == counted("xla")
    assert float(jnp.abs(grads["A_log"]).sum()) > 0


@pytest.mark.parametrize("channels,chunk,block", [
    (5120, 64, 5120), (256, 64, 256), (5120, 128, 2560),
    (384, 2048, 128)])
def test_a_grid_step_holds_the_widest_channel_block_that_fits(channels,
                                                              chunk, block):
    """A grid step's channels: a multiple of 128 that divides the channels,
    the widest whose ``[chunk, block]`` tile stays within the limit."""
    assert ss.channel_block(channels, chunk) == block
