"""AST trace-purity lint (analysis/astlint.py): one positive and one
negative fixture per rule, plus the traced-context discovery that keeps
the host-side drivers (seeded sampling, wall timers, bench harnesses)
out of the traced-only rules."""
import json
import os
import textwrap

import pytest

from neuroimagedisttraining_tpu.analysis import astlint

PKG = os.path.join(os.path.dirname(__file__), "..",
                   "neuroimagedisttraining_tpu")


def _lint_src(tmp_path, src, rel="algorithms/mod.py", name="pkgfix"):
    root = tmp_path / name
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    return astlint.PackageLint(str(root)).lint()


def _rules(findings):
    return [f.rule for f in findings]


# -- bare-assert ------------------------------------------------------------

def test_bare_assert_flagged_on_contract_path(tmp_path):
    fs = _lint_src(tmp_path, """
        def check(x):
            assert x > 0, "positive"
            return x
        """, rel="robust/guard.py")
    assert _rules(fs) == ["bare-assert"]
    assert fs[0].line == 3


def test_bare_assert_allowed_on_allowlisted_module(tmp_path):
    fs = _lint_src(tmp_path, """
        def check(ops):
            assert len(ops) % 2 == 0
        """, rel="nas/visualize.py")
    assert fs == []


def test_explicit_raise_is_clean(tmp_path):
    fs = _lint_src(tmp_path, """
        def check(x):
            if x <= 0:
                raise ValueError("positive")
            return x
        """, rel="robust/guard.py")
    assert fs == []


# -- host-sync --------------------------------------------------------------

def test_item_call_flagged_in_jit_path_package(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax.numpy as jnp

        def readout(x):
            return jnp.sum(x).item()
        """, rel="parallel/mod.py")
    assert "host-sync" in _rules(fs)


def test_float_on_jnp_expression_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax.numpy as jnp

        def norm(x):
            return float(jnp.sqrt(jnp.sum(x * x)))
        """, rel="robust/mod.py")
    assert "host-sync" in _rules(fs)


def test_float_on_static_shape_is_clean(tmp_path):
    fs = _lint_src(tmp_path, """
        def rows(x):
            return float(x.shape[0]) + int(len(x))
        """, rel="robust/mod.py")
    assert fs == []


def test_np_asarray_on_jax_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        import numpy as np
        import jax.numpy as jnp

        def pull(x):
            return np.asarray(jnp.mean(x, axis=0))
        """, rel="algorithms/mod.py")
    assert "host-sync" in _rules(fs)


def test_host_sync_not_module_wide_outside_jit_path(tmp_path):
    # obs/ export helpers legitimately .item() host-side; the
    # module-wide host-sync family is jit-path packages only
    fs = _lint_src(tmp_path, """
        def to_scalar(v):
            return v.item()
        """, rel="obs/mod.py")
    assert fs == []


# -- np-on-jax --------------------------------------------------------------

def test_np_math_on_jax_value_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        import numpy as np
        import jax.numpy as jnp

        def bad(x):
            return np.mean(jnp.abs(x))
        """, rel="core/mod.py")
    assert "np-on-jax" in _rules(fs)


def test_np_math_on_host_value_is_clean(tmp_path):
    fs = _lint_src(tmp_path, """
        import numpy as np

        def ok(counts):
            return np.mean(counts)
        """, rel="core/mod.py")
    assert fs == []


# -- nondeterminism (traced-context only) -----------------------------------

def test_np_random_inside_jitted_fn_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def round_fn(x):
            noise = np.random.rand(4)
            return x + noise
        """, rel="algorithms/mod.py")
    assert "nondeterminism" in _rules(fs)


def test_np_random_in_host_driver_is_clean(tmp_path):
    # the seeded sampling contract (np.random.seed(round_idx)) lives in
    # HOST code — the traced-context discovery must not reach it
    fs = _lint_src(tmp_path, """
        import numpy as np

        def sample_clients(round_idx, n, k):
            np.random.seed(round_idx)
            return np.random.choice(range(n), k, replace=False)
        """, rel="algorithms/mod.py")
    assert fs == []


def test_print_and_time_in_scan_body_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        import time
        import jax

        def driver(xs):
            def body(carry, x):
                print(carry)
                t = time.perf_counter()
                return carry + x, t
            return jax.lax.scan(body, 0.0, xs)
        """, rel="parallel/mod.py")
    assert _rules(fs).count("nondeterminism") == 2


def test_traced_discovery_follows_same_module_calls(tmp_path):
    # fixpoint: a helper called from a jitted fn is traced too
    fs = _lint_src(tmp_path, """
        import jax
        import numpy as np

        def helper(x):
            return x * np.random.rand()

        @jax.jit
        def round_fn(x):
            return helper(x)
        """, rel="algorithms/mod.py")
    assert "nondeterminism" in _rules(fs)


def test_traced_discovery_follows_self_methods_across_modules(tmp_path):
    root = tmp_path / "pkgx"
    (root / "algorithms").mkdir(parents=True)
    (root / "core").mkdir()
    (root / "algorithms" / "sub.py").write_text(textwrap.dedent("""
        import jax

        class Sub:
            def build(self):
                def round_fn(x):
                    return self._shared_body(x)
                self._round_jit = jax.jit(round_fn)
        """))
    (root / "core" / "base.py").write_text(textwrap.dedent("""
        import numpy as np

        class Base:
            def _shared_body(self, x):
                return x + np.random.rand()
        """))
    pl = astlint.PackageLint(str(root))
    fs = pl.lint()
    assert [(f.rule, f.file) for f in fs] == [
        ("nondeterminism", "pkgx/core/base.py")]


# -- tracer-branch ----------------------------------------------------------

def test_python_if_on_traced_predicate_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def round_fn(x):
            if jnp.any(x > 0):
                return x
            return -x
        """, rel="robust/mod.py")
    assert "tracer-branch" in _rules(fs)


def test_static_predicate_if_is_clean(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def round_fn(x):
            if jnp.issubdtype(x.dtype, jnp.inexact):
                return x
            return x.astype(jnp.float32)
        """, rel="robust/mod.py")
    assert fs == []


# -- deprecated-timer -------------------------------------------------------

def test_deprecated_timer_import_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        from ..utils.profiling import Timer

        def bench():
            return Timer()
        """, rel="obs/mod.py")
    assert "deprecated-timer" in _rules(fs)


# -- contract-path auto-discovery on the real tree --------------------------

def test_contract_discovery_covers_the_drifted_modules():
    """The hand-maintained CONTRACT_PATHS list of the retired
    tests/test_no_bare_assert.py had drifted: these modules were
    unlisted. Auto-discovery covers them by construction."""
    pl = astlint.PackageLint(PKG)
    contract = set(pl.contract_modules())
    for rel in ("algorithms/ditto.py", "comm/grpc_backend.py",
                "comm/tcp.py", "comm/local.py", "robust/faults.py",
                "robust/guard.py", "robust/recovery.py",
                "analysis/astlint.py", "analysis/gate.py"):
        assert rel in contract, rel


def test_allowlist_entries_exist():
    """Exact-path entries must name real modules (else the pin is
    stale); prefix entries (trailing /) cover codegen output dirs that
    may be absent on a fresh checkout — comm/_generated/ is gitignored
    and only exists after the grpc codegen runs."""
    pl = astlint.PackageLint(PKG)
    for rel in astlint.NON_CONTRACT_ALLOWLIST:
        if rel.endswith("/"):
            assert not os.path.isfile(
                os.path.join(PKG, rel.rstrip("/")))
        else:
            assert rel in pl.modules, f"stale allowlist entry {rel}"


def test_allowlist_prefix_covers_generated_modules(tmp_path):
    root = tmp_path / "pkgg"
    gen = root / "comm" / "_generated"
    gen.mkdir(parents=True)
    (gen / "stub_pb2.py").write_text(
        "def check(x):\n    assert x\n")
    assert astlint.PackageLint(str(root)).lint() == []


# -- xfail hygiene ----------------------------------------------------------

def _write_ledger(path, ids):
    path.write_text(json.dumps({
        "version": 1,
        "entries": [{"id": i, "reason": "pinned"} for i in ids]}))


def test_xfail_without_reason_flagged(tmp_path):
    (tmp_path / "test_x.py").write_text(textwrap.dedent("""
        import pytest

        @pytest.mark.xfail
        def test_broken():
            raise AssertionError
        """))
    ledger = tmp_path / "ledger.json"
    _write_ledger(ledger, ["test_x.py::test_broken"])
    fs = astlint.check_xfails(str(tmp_path), str(ledger))
    assert _rules(fs) == ["xfail-reason"]


def test_unledgered_xfail_flagged(tmp_path):
    (tmp_path / "test_x.py").write_text(textwrap.dedent("""
        import pytest

        @pytest.mark.xfail(reason="known drift", strict=False)
        def test_broken():
            raise AssertionError
        """))
    ledger = tmp_path / "ledger.json"
    _write_ledger(ledger, [])
    fs = astlint.check_xfails(str(tmp_path), str(ledger))
    assert _rules(fs) == ["xfail-ledger"]


def test_stale_ledger_entry_flagged(tmp_path):
    (tmp_path / "test_x.py").write_text("def test_ok():\n    pass\n")
    ledger = tmp_path / "ledger.json"
    _write_ledger(ledger, ["test_x.py::test_gone"])
    fs = astlint.check_xfails(str(tmp_path), str(ledger))
    assert _rules(fs) == ["xfail-ledger"]


def test_pinned_xfails_are_clean(tmp_path):
    (tmp_path / "test_x.py").write_text(textwrap.dedent("""
        import pytest

        @pytest.mark.xfail(reason="known drift", strict=False)
        def test_broken():
            raise AssertionError
        """))
    ledger = tmp_path / "ledger.json"
    _write_ledger(ledger, ["test_x.py::test_broken"])
    assert astlint.check_xfails(str(tmp_path), str(ledger)) == []


def test_xfail_ids_qualify_enclosing_class(tmp_path):
    """Two same-named tests in different classes must not share a pin:
    the second xfail would otherwise ride the first's ledger entry."""
    (tmp_path / "test_x.py").write_text(textwrap.dedent("""
        import pytest

        class TestA:
            @pytest.mark.xfail(reason="pinned drift", strict=False)
            def test_roundtrip(self):
                raise AssertionError

        class TestB:
            @pytest.mark.xfail(reason="new debt", strict=False)
            def test_roundtrip(self):
                raise AssertionError
        """))
    ids = [s["id"] for s in astlint.scan_xfails(str(tmp_path))]
    assert ids == ["test_x.py::TestA.test_roundtrip",
                   "test_x.py::TestB.test_roundtrip"]
    ledger = tmp_path / "ledger.json"
    _write_ledger(ledger, ["test_x.py::TestA.test_roundtrip"])
    fs = astlint.check_xfails(str(tmp_path), str(ledger))
    assert _rules(fs) == ["xfail-ledger"]
    assert fs[0].detail == "test_x.py::TestB.test_roundtrip"


def test_param_marks_and_pytestmark_are_scanned(tmp_path):
    """xfail marks smuggled through pytest.param(marks=...) or a
    module-level pytestmark are the same test debt as a decorator —
    both need the reason and the ledger pin."""
    (tmp_path / "test_x.py").write_text(textwrap.dedent("""
        import pytest

        pytestmark = pytest.mark.xfail(reason="whole module drifts")

        @pytest.mark.parametrize("v", [
            1,
            pytest.param(2, marks=pytest.mark.xfail(reason="case 2")),
        ])
        def test_cases(v):
            assert v == 1
        """))
    sites = {s["id"]: s for s in astlint.scan_xfails(str(tmp_path))}
    assert "test_x.py::<module>" in sites
    assert "test_x.py::test_cases" in sites
    assert sites["test_x.py::test_cases"]["reason"] == "case 2"
    ledger = tmp_path / "ledger.json"
    _write_ledger(ledger, ["test_x.py::<module>"])
    fs = astlint.check_xfails(str(tmp_path), str(ledger))
    assert _rules(fs) == ["xfail-ledger"]
    assert fs[0].detail == "test_x.py::test_cases"


def test_two_marks_on_one_line_both_scanned(tmp_path):
    """The Call-vs-inner-Attribute dedupe keys on column too, so a
    one-line parametrize list with two xfail marks keeps both — the
    second mark's missing reason= must still surface."""
    (tmp_path / "test_x.py").write_text(
        "import pytest\n"
        "@pytest.mark.parametrize('v', ["
        "pytest.param(2, marks=pytest.mark.xfail(reason='a')), "
        "pytest.param(3, marks=pytest.mark.xfail)])\n"
        "def test_cases(v):\n    assert v\n")
    sites = astlint.scan_xfails(str(tmp_path))
    assert len(sites) == 2
    assert sorted(s["reason"] for s in sites) == ["", "a"]
    ledger = tmp_path / "ledger.json"
    _write_ledger(ledger, ["test_x.py::test_cases"])
    fs = astlint.check_xfails(str(tmp_path), str(ledger))
    assert _rules(fs) == ["xfail-reason"]


def test_imperative_xfail_needs_reason_but_no_pin(tmp_path):
    (tmp_path / "test_x.py").write_text(textwrap.dedent("""
        import pytest

        def test_env_gated():
            pytest.xfail()
        """))
    ledger = tmp_path / "ledger.json"
    _write_ledger(ledger, [])
    fs = astlint.check_xfails(str(tmp_path), str(ledger))
    assert _rules(fs) == ["xfail-reason"]


def test_xfails_in_subdirectories_are_scanned(tmp_path):
    sub = tmp_path / "integration"
    sub.mkdir()
    (sub / "test_deep.py").write_text(textwrap.dedent("""
        import pytest

        @pytest.mark.xfail(reason="deep drift", strict=False)
        def test_deep():
            raise AssertionError
        """))
    ids = [s["id"] for s in astlint.scan_xfails(str(tmp_path))]
    assert ids == ["integration/test_deep.py::test_deep"]


def test_malformed_ledger_entry_is_value_error(tmp_path):
    ledger = tmp_path / "ledger.json"
    ledger.write_text(json.dumps(
        {"version": 1, "entries": [{"reason": "no id"}]}))
    with pytest.raises(ValueError):
        astlint.load_xfail_ledger(str(ledger))


def test_repo_xfails_match_committed_ledger():
    tests_dir = os.path.dirname(__file__)
    fs = astlint.check_xfails(
        tests_dir, os.path.join(tests_dir, "xfail_ledger.json"))
    assert fs == [], [f.render() for f in fs]


# -- stable suppression keys ------------------------------------------------

def test_finding_keys_are_line_number_free(tmp_path):
    """Baseline keys must survive unrelated line drift: same source,
    different position, same key."""
    a = _lint_src(tmp_path, """
        def f(x):
            assert x
        """, rel="robust/a.py", name="p1")
    b = _lint_src(tmp_path, """
        # padding
        # padding


        def f(x):
            assert x
        """, rel="robust/a.py", name="p2")
    ka = a[0].key.split(":", 2)[2]
    kb = b[0].key.split(":", 2)[2]
    assert ka == kb == "assert x"


# -- donation-use-after -----------------------------------------------------

def test_use_after_donation_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        def drive(algo, state, r):
            new_state, rec = algo.run_round(state, r)
            norm = state.global_params
            return new_state, norm
        """, rel="experiments/driver.py")
    assert _rules(fs) == ["donation-use-after"]
    assert fs[0].line == 4


def test_same_statement_rebind_is_clean(tmp_path):
    fs = _lint_src(tmp_path, """
        def drive(algo, state, rounds):
            for r in range(rounds):
                state, rec = algo.run_round(state, r)
            return state
        """, rel="experiments/driver.py")
    assert fs == []


def test_read_before_donation_and_clone_are_clean(tmp_path):
    fs = _lint_src(tmp_path, """
        def drive(algo, state, r):
            old_pers = state.personal_params
            new_state, rec = algo.run_round(algo.clone_state(state), r)
            return new_state, old_pers, state
        """, rel="experiments/driver.py")
    # arg0 is a clone_state(...) Call, not the state Name — the
    # original deliberately survives (borrow semantics)
    assert fs == []


def test_single_arg_same_named_method_is_not_donating(tmp_path):
    # comm.cross_silo.run_round(round_idx) shares the name but takes no
    # state: the >= 2 positional-args guard keeps it out of the rule
    fs = _lint_src(tmp_path, """
        def loop(self, rounds):
            for r in range(rounds):
                rec = self.run_round(r)
                history = [r, rec]
            return history
        """, rel="comm/driver.py")
    assert fs == []


def test_rebind_after_window_closes_it(tmp_path):
    fs = _lint_src(tmp_path, """
        def drive(algo, state, r):
            out = algo._round_jit(state, r)
            state = out[0]
            return state.global_params
        """, rel="experiments/driver.py")
    assert fs == []
