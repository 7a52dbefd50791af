"""Property-based validation of the SLO engine's streaming quantile
estimators (hypothesis) against exact ``np.quantile``.

obs/slo.py evaluates SLO objectives with O(1)-memory streaming
estimators; these properties pin them to ground truth for arbitrary
streams:

* the WINDOWED estimator is exact — its value equals
  ``np.quantile(window, q, method='linear')`` on the identical trailing
  window, at every step of the stream;
* the P² estimator (``w=0``, whole-run) keeps what five markers
  guarantee for a stream in ANY order: an estimate inside the stream's
  hull, marker heights in order, marker positions strictly increasing
  from 1 to the count. Its accuracy claim, the quantile ENVELOPE
  ``[Q(q - 0.1), Q(q + 0.1)]``, is about streams whose order carries no
  trend, and is held on seeded i.i.d. streams in tests/test_slo.py: an
  ordered stream (a long low run after the markers settled high) drags
  the estimate outside it, and the draw that showed so stays here as
  an explicit example;
* the fixed-reservoir estimator is EXACT (nearest-rank) while the
  stream fits its reservoir;
* all three are deterministic: the same stream yields the same
  estimate sequence (the bit-reproducible-verdicts contract).

The concrete (hypothesis-free) twins of these checks run in
tests/test_slo.py on every host; where hypothesis is not installed,
``tests/_hypothesis_fallback.py`` supplies a deterministic example
generator so the properties still run (no silent skip).
"""
import numpy as np
import pytest

# hypothesis is an optional test extra (pyproject `test`); without it
# the deterministic shim keeps the properties exercised (weaker — no
# shrinking — but never a silent skip)
try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import (
        example,
        given,
        settings,
        strategies as st,
    )

from neuroimagedisttraining_tpu.obs.slo import (
    P2Quantile,
    ReservoirQuantile,
    WindowedQuantile,
)

_QS = [0.5, 0.9, 0.95, 0.99]


@settings(max_examples=60, deadline=None)
@given(data=st.data(),
       q=st.sampled_from(_QS),
       window=st.integers(2, 32))
def test_windowed_quantile_exact_on_every_window(data, q, window):
    xs = data.draw(st.lists(
        st.floats(-1e6, 1e6, allow_nan=False, allow_infinity=False),
        min_size=1, max_size=80))
    est = WindowedQuantile(q, window=window)
    for i, x in enumerate(xs):
        est.observe(x)
        ref = np.quantile(np.asarray(xs[max(0, i + 1 - window):i + 1],
                                     dtype=np.float64), q)
        np.testing.assert_allclose(est.value(), ref, rtol=1e-9,
                                   atol=1e-9)


#: hypothesis's falsifying draw for the envelope this test used to claim
#: for every order (q 0.5: estimate -7086204.77 against Q(0.6) =
#: -7099563.4): high values first, then a long run near -1e7
_P2_ORDERED_STREAM = [
    0, 1182461, 1350832, 2313865, 2669832, 2940272, 3016182, 5689917,
    6750946, -7556906, 9289823, 9486978, 9786083, -9935965, -9936090,
    -9939302, -9941733, -9942203, -9945070, -9946290, -9949304, -9949527,
    -9950843, -9952787, -9953513, -9955571, -9960420, -9960518, -9964230,
    -9965042, -7310061, -7402419, -7494665, -7515060, -7527440, -7527459,
    -7556738, -9960822, -6783817, -9986292, -9987688, -9967162, -9989517,
    -9992715, -9992748, -8026393, -9995638, -9999689, -8081538, 6, -5, 5,
    -4, 4, -3, 3, -2, 2, -1, 1]


@settings(max_examples=60, deadline=None)
@given(xs=st.lists(st.integers(-10_000_000, 10_000_000),
                   min_size=60, max_size=300, unique=True),
       q=st.sampled_from(_QS))
@example(xs=_P2_ORDERED_STREAM, q=0.5)
def test_p2_quantile_within_exact_envelope(xs, q):
    # what the five markers guarantee whatever the order of the stream
    # (the module docstring has why the quantile envelope is not among it)
    arr = np.asarray(xs, dtype=np.float64)
    est = P2Quantile(q)
    for n, x in enumerate(arr, 1):
        est.observe(float(x))
        if n >= 5:
            assert est._h == sorted(est._h), (n, est._h)
            assert est._pos[0] == 1.0 and est._pos[4] == float(n)
            assert all(a < b for a, b in zip(est._pos, est._pos[1:])), \
                (n, est._pos)
            assert est._h[0] == arr[:n].min()
            assert est._h[4] == arr[:n].max()
    assert arr.min() <= est.value() <= arr.max()


@settings(max_examples=60, deadline=None)
@given(data=st.data(), q=st.sampled_from(_QS))
def test_reservoir_quantile_exact_within_capacity(data, q):
    xs = data.draw(st.lists(
        st.floats(-1e6, 1e6, allow_nan=False, allow_infinity=False),
        min_size=1, max_size=100))
    est = ReservoirQuantile(q, reservoir_size=128)
    for x in xs:
        est.observe(x)
    s = sorted(xs)
    # metrics.Distribution's reservoir is the FULL sample here, so the
    # nearest-rank estimate is exact by construction
    assert est.value() == s[min(len(s) - 1,
                                max(0, int(round(q * (len(s) - 1)))))]


@settings(max_examples=30, deadline=None)
@given(data=st.data(), q=st.sampled_from(_QS))
def test_estimators_deterministic_per_stream(data, q):
    xs = data.draw(st.lists(
        st.floats(-1e3, 1e3, allow_nan=False, allow_infinity=False),
        min_size=1, max_size=60))

    def run(mk):
        e = mk()
        out = []
        for x in xs:
            e.observe(x)
            out.append(e.value())
        return out

    for mk in (lambda: WindowedQuantile(q, 8),
               lambda: P2Quantile(q),
               lambda: ReservoirQuantile(q)):
        assert run(mk) == run(mk)
