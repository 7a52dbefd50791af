"""Scope and pass from ``op_name``s as the compiler really prints them, the
three readers on a hand-built trace with known answers, and the program's
side of the contract: every scope a metric file asks for is in the program."""
import glob
import json
import os

import pytest
from conftest import BENCH

from benchmarks.lib import reduce_trace as rt
from benchmarks.lib import scopes
from benchmarks.readers import (scope_coverage, scope_pass_ms_per_round,
                                scope_roofline)

STEP = ("jit(round_fn)/local_train/while/body/closed_call/vmap()/while/body/"
        "closed_call/")
# recorded from compiled rounds (the tiny round on the CPU, the AlexNet3D
# round compiled for a v5e), PR 24
FWD = STEP + "jvp(AlexNet3DS2D)/S2DStemStage_0/stem/conv/conv_general_dilated"
BWD = STEP + "transpose(jvp(AlexNet3DS2D))/S2DStemStage_0/stem/norm/reduce_sum"
JOINED = (STEP + "transpose(jvp(AlexNet3DS2D))/S2DStemStage_0/stem/conv/"
          "transpose;transpose(jvp(AlexNet3DS2D))/S2DStemStage_0/stem/conv/"
          "reshape")
JOINED_FAR = ("jit(round_fn)/local_train/while/body/closed_call/vmap()/mul;"
              "while/body/closed_call")
PREFIX_LOST = "transpose(jvp(SmallCNN3DS2D))/GroupNorm_0"
WRONG_PREFIX = ("jit(round_fn)/jit(_threefry_split)/make_snip_score_fn."
                "<locals>.snip_scores.<locals>.body/add")
VMAPPED_SCOPE = ("jit(round_fn)/local_train/while/body/closed_call/"
                 "vmap(batch_gather)/vmap(jit(_take))/gather")
TAKE = STEP + "batch_gather/jit(_take)/select_n"
NO_SCOPE_IN_JVP = STEP + "transpose(jvp())/mul"
BARE = "reduce_sum"


def test_parse_unwraps_transforms_and_splits_joined_names():
    (segments, wraps), = scopes.parse(FWD)
    assert segments[:3] == ("round_fn", "local_train", "while")
    assert segments[-5:] == ("AlexNet3DS2D", "S2DStemStage_0", "stem", "conv",
                             "conv_general_dilated")
    assert wraps[0] == ("jit",) and ("vmap",) in wraps and ("jvp",) in wraps
    assert scopes.transforms(FWD) == {"jit", "vmap", "jvp"}
    (segments, wraps), = scopes.parse(PREFIX_LOST)
    assert segments == ("SmallCNN3DS2D", "GroupNorm_0")
    assert wraps == (("transpose", "jvp"), ())
    assert len(scopes.parse(JOINED)) == 2
    assert scopes.parse(JOINED)[1][0][0] == "AlexNet3DS2D"
    assert scopes.parse(BARE) == ((("reduce_sum",), ((),)),)
    assert scopes.parse("") == (((), ()),)
    assert scopes.transforms(VMAPPED_SCOPE) == {"jit", "vmap"}


@pytest.mark.parametrize("name,want", [
    (FWD, "fwd"), (BWD, "bwd"), (JOINED, "bwd"), (PREFIX_LOST, "bwd"),
    (NO_SCOPE_IN_JVP, "bwd"), (STEP + "jvp()/sub", "fwd"),
    (JOINED_FAR, None), (WRONG_PREFIX, None), (TAKE, None), (BARE, None),
    ("", None), (STEP + "optimizer/mul", None)])
def test_direction(name, want):
    assert scopes.direction(name) == want


def test_under_matches_whole_segments_anywhere():
    assert scopes.under(FWD, "stem/conv") and scopes.under(FWD, "stem")
    assert scopes.under(FWD, "local_train")
    assert not scopes.under(FWD, "stem/norm") and not scopes.under(FWD, "conv/stem")
    assert not scopes.under(FWD, "S2DStemStage") and not scopes.under(FWD, "ste")
    # a wrapped scope, a second part of a joined name, a lost prefix
    assert scopes.under(VMAPPED_SCOPE, "batch_gather")
    assert scopes.under(JOINED_FAR.replace("mul;", "mul;optimizer/"), "optimizer")
    assert scopes.under(JOINED, "stem/conv")
    assert scopes.under(PREFIX_LOST, "GroupNorm_0")
    assert not scopes.under(PREFIX_LOST, "local_train")
    # a Python function's qualified name is a segment like any other: only
    # the names the program sets are ever asked for
    assert not scopes.under(WRONG_PREFIX, "snip_scores")
    assert not scopes.under(BARE, "stem") and not scopes.under("", "stem")


def traced_round(rounds=1):
    """One device, ns. The round: a cohort gather XLA expanded into a loop
    that kept the gather's name over a nameless body; the loop of
    local_train holding a batch gather, a forward and two backward stem
    fusions (one with its prefix lost), a bare ``reduce_sum`` XLA made, an
    optimizer fusion and 50 ns of loop overhead; then the personal scatter
    and a nameless copy at top level. An eval program follows."""
    step = STEP
    ops = [
        rt.Op("while.9", 0, 100, "jit_round_fn",
              "jit(round_fn)/cohort_gather/jit(_take)/gather"),
        rt.Op("dynamic-slice_fusion.2", 10, 90, "jit_round_fn", ""),
        rt.Op("while.1", 100, 1000, "jit_round_fn",
              "jit(round_fn)/local_train/while"),
        rt.Op("copy.268", 100, 200, "jit_round_fn", TAKE),
        rt.Op("fusion.566", 200, 400, "jit_round_fn", FWD),
        rt.Op("fusion.567", 400, 450, "jit_round_fn",
              step + "jvp(AlexNet3DS2D)/S2DStemStage_0/stem/pool/"
              "reduce_window_max"),
        rt.Op("fusion.570", 450, 500, "jit_round_fn",
              step + "jvp(AlexNet3DS2D)/Conv3d_0/Conv_0/conv_general_dilated"),
        rt.Op("fusion.607", 500, 800, "jit_round_fn", BWD),
        rt.Op("select-and-scatter.11", 800, 900, "jit_round_fn",
              "transpose(jvp(AlexNet3DS2D))/S2DStemStage_0/stem/pool/"
              "select_and_scatter"),
        rt.Op("reduce.3", 900, 920, "jit_round_fn", BARE),
        rt.Op("fusion.615", 920, 950, "jit_round_fn", step + "optimizer/mul"),
        rt.Op("fusion.700", 1000, 1040, "jit_round_fn",
              "jit(round_fn)/personal_update/scatter"),
        rt.Op("copy-done.5", 1040, 1050, "jit_round_fn", ""),
        rt.Op("fusion.9", 1100, 1300, "jit_eval_all",
              "jit(eval_all)/S2DStemStage_0/stem/conv/conv_general_dilated"),
    ]
    rt.nest(ops)
    return rt.Trace({"/device:TPU:0": ops}, [], rounds)


def context(rounds=1):
    # one conv, one pointwise row and one the model does not scope
    layers = [
        {"name": "conv1", "kind": "conv", "taps": 125, "in": (8, 8, 8, 1),
         "out": (2, 2, 2, 64)},
        {"name": "pool1", "kind": "pointwise", "in": (2, 2, 2, 64),
         "out": (1, 1, 1, 64)},
        {"name": "conv2", "kind": "conv", "taps": 27, "in": (1, 1, 1, 64),
         "out": (1, 1, 1, 8), "input_grad": True}]
    return {"trace": traced_round(rounds), "details": {},
            "counters": {"steps_per_round_per_chip": 2}, "layers": layers,
            "batch": 4, "itemsize": 2,
            "peaks": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}}


def test_scope_pass_ms_per_round():
    ctx = context(rounds=2)
    def read(ctx, **kw):
        return scope_pass_ms_per_round.read(ctx, "jit_round_fn", **kw)
    # the expanded gather's loop covers its nameless body
    assert read(ctx, scope="cohort_gather") == pytest.approx(50e-6)
    assert read(ctx, scope="batch_gather") == pytest.approx(50e-6)
    assert read(ctx, scope="optimizer") == pytest.approx(15e-6)
    assert read(ctx, scope="personal_update") == pytest.approx(20e-6)
    assert read(ctx, direction="fwd") == pytest.approx(150e-6)
    assert read(ctx, direction="bwd") == pytest.approx(200e-6)
    assert read(ctx, scope="stem/conv", direction="fwd") == pytest.approx(100e-6)
    assert read(ctx, scope="stem/conv", direction="bwd") is None
    assert read(ctx, scope="stem/pool", direction="bwd") == pytest.approx(50e-6)
    # the eval program's ops are not the round's, whatever name they got
    assert read(ctx, scope="stem") == pytest.approx(650 / 2 * 1e-6)
    assert scope_pass_ms_per_round.read(ctx, "jit_eval", scope="stem") == \
        pytest.approx(100e-6)
    # a missing scope is explained only where the round has names at all
    assert read(ctx, scope="guard") is None
    assert "empty the compile cache" in ctx["details"]["scopes_missing"]["guard"]
    assert set(ctx["details"]["scopes_missing"]) == {"guard"}
    ops = [rt.Op("fusion.1", 0, 10, "jit_round_fn", "")]
    rt.nest(ops)
    bare = {"trace": rt.Trace({"d": ops}, [], 1), "details": {}}
    assert read(bare, scope="stem") is None and bare["details"] == {}


def test_scope_coverage_adds_up_by_name():
    ctx = context()
    listed = ["cohort_gather", "batch_gather", "optimizer", "stem",
              "personal_update", "aggregate"]
    share = scope_coverage.read(ctx, "jit_round_fn", listed)
    # uncovered: the loop's own 50 ns, the bare reduce, the top-level copy
    assert share == pytest.approx(100 * (50 + 20 + 10) / 1050)
    d = ctx["details"]["scope_coverage"]
    table = d["self_ms_per_round"]
    assert table["cohort_gather"] == {"-": pytest.approx(100e-6)}
    assert table["stem"] == {"bwd": pytest.approx(400e-6),
                             "fwd": pytest.approx(250e-6)}
    assert table["-"] == {"-": pytest.approx(80e-6),
                          "fwd": pytest.approx(50e-6)}
    assert "aggregate" not in table
    assert sum(v for row in table.values() for v in row.values()) == \
        pytest.approx(d["total_ms_per_round"]) == pytest.approx(1050e-6)
    assert [k for k, _ in d["uncovered_ops_ms_per_round"]] == [
        "jit_round_fn/while.1 [local_train/while]",
        "jit_round_fn/reduce.3 [reduce_sum]", "jit_round_fn/copy-done.5"]
    assert scope_coverage.read(ctx, "jit_nothing", listed) is None


def test_scope_roofline_takes_the_rows_the_model_has():
    ctx = context()
    layers = {"conv1": "stem/conv", "pool1": "stem/pool",
              "stem_pool": "stem/pool"}
    value = scope_roofline.read(ctx, "jit_round_fn", "stem", layers)
    d = ctx["details"]["stem_roofline"]
    assert [(r["layer"], r["pass"]) for r in d["rows"]] == [
        ("conv1", "fwd"), ("conv1", "bwd"), ("pool1", "fwd"), ("pool1", "bwd")]
    assert d["measured_s_per_round"] == pytest.approx(650e-9)
    assert value == pytest.approx(100 * d["floor_s_per_round"] / 650e-9)
    conv_fwd, conv_bwd = d["rows"][:2]
    # 2 steps x batch 4 x (512 + 8000 + 512) elements x 2 bytes at 1e9 B/s
    assert conv_fwd["bound"] == "memory"
    assert conv_fwd["floor_s_per_round"] == pytest.approx(2 * 4 * 9024 * 2e-9)
    assert conv_fwd["measured_s_per_round"] == pytest.approx(200e-9)
    assert conv_fwd["share_pct"] == pytest.approx(
        100 * conv_fwd["floor_s_per_round"] / 200e-9)
    # XLA timed the conv's backward under the norm's name: no time, no share
    assert conv_bwd["measured_s_per_round"] == 0 and conv_bwd["share_pct"] is None
    assert d["passes"]["bwd"]["measured_s_per_round"] == pytest.approx(400e-9)
    assert d["floor_s_per_round"] == pytest.approx(
        sum(p["floor_s_per_round"] for p in d["passes"].values()))
    assert scope_roofline.read(ctx, "jit_round_fn", "stem",
                               {"stem_norm": "stem/norm"}) is None
    assert scope_roofline.read(ctx, "jit_round_fn", "guard", layers) is None
    assert "guard" in ctx["details"]["scopes_missing"]


def metric_scopes():
    """Every scope a metric file names, with the files that name it."""
    found = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.json"))):
        with open(path) as f:
            args = json.load(f).get("args", {})
        named = [args.get("scope")] + list(args.get("scopes", ())) \
            + list((args.get("layers") or {}).values())
        for scope in filter(None, named):
            found.setdefault(scope, []).append(os.path.basename(path))
    return found


def test_every_scope_a_metric_names_is_in_the_program():
    """A rename in the program cannot silently empty a metric: each scope is
    looked for in the compiled tiny rounds (one at frac 0.5 with the guard,
    numerics and the eval cache on, one with a robust aggregate) and in the
    lowered training steps of the two full models (the builders are the
    tier-1 test's, ``tests/test_round_scopes.py``)."""
    from tests import test_round_scopes as program

    wanted = metric_scopes()
    assert {"cohort_gather", "personal_update", "batch_gather", "optimizer",
            "stem", "stem/conv", "stem/norm", "stem/pool", "local_train",
            "aggregate"} <= set(wanted)
    # names are read out of compiled programs: keep a stale executable of the
    # persistent compile cache from answering with the names of another day
    with program.metadata_in_cache_key():
        names = program.compiled_round_names(
            "fedavg", 0.5, guard=True, fault_spec="nan=0.3", eval_cache=True,
            obs_numerics=True)
        names |= program.compiled_round_names(
            "fedavg", 0.5, robust_agg="trimmed_mean")
        for model_name in program.STEM_VOLUMES:
            names |= program.lowered_step_names(model_name)
    missing = {scope: files for scope, files in wanted.items()
               if not any(scopes.under(n, scope) for n in names)}
    assert not missing, f"scopes no instruction of the program is under: {missing}"
