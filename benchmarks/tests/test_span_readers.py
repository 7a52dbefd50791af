"""The three span readers on a made-up span tree and a made-up trace: the
arithmetic of each of the ten metrics, where set-up ends, a rise, a span that
is not there, and the account's closure. Times are seconds on the tracer's
clock; nothing here is a device number."""
import pytest
from conftest import MANIFEST

from benchmarks.lib import manifest as mf
from benchmarks.lib import reduce_trace as rt
from benchmarks.lib import spans as sp
from benchmarks.readers import driver_host, setup_spans_s, span_memory_gib

GIB = 2 ** 30
SETUP_S = 27.5          # process start lies 7 s before the tracer's origin
TRACE_ZERO = 39.5       # the tracer's time at which the profiler's trace starts
UNIX = 1_791_000_000_000_000_000


def ev(span_id, parent, name, start, end, depth, in_use=None, peak=None,
       **args):
    if in_use is not None:
        args.update(hbm_in_use_bytes=int(in_use * GIB),
                    hbm_peak_bytes=int(peak * GIB))
    return {"name": name, "ph": "X", "ts": start * 1e6,
            "dur": (end - start) * 1e6, "span_id": span_id, "parent": parent,
            "args": {"depth": depth, **args}}


def events():
    block = [(20, 21.0, 23.0), (30, 23.0, 25.0), (40, 25.0, 30.0),
             (50, 30.0, 32.1)]
    return [
        ev(1, None, "import_program", -5.0, -5.0 + 1e-6, 0),
        ev(2, None, "cohort", 1.0, 3.0, 0, 0.5, 0.5),
        ev(3, None, "build", 3.0, 3.5, 0, 1.0, 1.0),
        ev(4, None, "init_state", 3.5, 7.5, 0, 3.0, 3.5),
        ev(5, 4, "init_params", 3.6, 5.0, 1),
        ev(6, 5, "compile/backend", 3.7, 4.7, 2),
        ev(7, 4, "snip_mask", 5.0, 7.0, 1),
        ev(8, 7, "compile/trace", 5.1, 5.5, 2),
        ev(9, 7, "compile/trace", 5.2, 5.4, 2),    # traced inside the other
        ev(10, 7, "compile/lower", 5.5, 5.8, 2),
        ev(11, 7, "compile/backend", 5.8, 6.8, 2),
        ev(12, None, "reference_check", 7.5, 17.5, 0, 3.0, 6.0),
        ev(13, None, "warmup", 17.5, 20.5, 0, 3.5, 6.0),
        ev(14, 13, "run", 17.6, 20.4, 1, rounds=2),
        ev(15, 14, "round", 17.6, 20.0, 2, step=0),
        ev(16, 15, "dispatch_round", 17.7, 19.9, 3, round=0),
        ev(17, 16, "compile/trace", 17.8, 18.0, 4, round=0),
        ev(18, None, "state_check", 33.0, 34.0, 0, 3.5, 7.0),
    ] + [ev(i, None, "run", a, b, 0, 3.5, 7.0, rounds=2)
         for i, a, b in block] + [
        ev(41, 40, "round", 25.0, 29.5, 1, step=0),
        ev(42, 41, "flush", 25.5, 29.4, 2, round=1),
        ev(21, 20, "round", 21.0, 22.9, 1, step=0),
        ev(22, 21, "flush", 21.5, 22.8, 2, round=1),
        ev(60, None, "run", 40.0, 43.0, 0, 3.5, 7.0, rounds=3),
        ev(61, 60, "round", 40.0, 40.9, 1, step=0),
        ev(62, 61, "sample", 40.0, 40.005, 2, round=0),
        ev(63, 61, "dispatch_round", 40.01, 40.02, 2, round=0),
        ev(65, 60, "round", 41.0, 42.0, 1, step=1),
        ev(66, 65, "flush", 41.2, 41.9, 2, round=0),
        ev(67, 60, "round", 42.0, 42.95, 1, step=2),
        ev(68, 67, "flush", 42.1, 42.9, 2, round=1),
        ev(69, 60, "flush", 42.95, 42.97, 1, round=2),
    ]


def made_up_trace():
    """One chip whose ops leave three gaps: two that begin while a flush is
    open and one after the last flush; the host's twins of the traced
    block's spans, the rounds' 2 us late."""
    def at(t):
        return (t - TRACE_ZERO) * 1e9

    ops = [rt.Op("fusion.1", at(40.1), at(41.25), "jit_round_fn"),
           rt.Op("fusion.2", at(41.4), at(42.5), "jit_round_fn"),
           rt.Op("fusion.3", at(42.97), at(42.99), "jit_round_fn"),
           rt.Op("fusion.4", at(43.1), at(43.2), "jit_round_fn")]
    rt.nest(ops)
    host = [("traced_rounds", at(39.9), at(43.3)),
            ("run", at(40.0), at(43.0)), ("run", at(41.0), at(41.1)),
            ("np.asarray_jax.Array_", at(41.2), at(41.9)),
            ("np.asarray_jax.Array_", at(42.1), at(42.9))]
    host += [("round", at(a) + 2000, at(b))
             for a, b in ((40.0, 40.9), (41.0, 42.0), (42.0, 42.95))]
    return rt.Trace({"/device:TPU:0": ops}, host, 3)


class FakeTracer:
    origin_unix_ns = UNIX

    def __init__(self, events):
        self.events = events


@pytest.fixture
def ctx(monkeypatch):
    tracer = FakeTracer(events())
    monkeypatch.setattr(sp, "program_tracer", lambda: tracer)
    return {"trace": made_up_trace(), "counters": {"setup_s": SETUP_S},
            "details": {}, "tracer": tracer}


def metric(ctx, name):
    """The metric ``name`` through its own file, as the harness reads it."""
    cell = mf.load_cell(MANIFEST, "alexnet3d_abcd.train")
    (spec,) = [s for e, s in cell.per_layer if e["name"] == name]
    reader = {"setup_spans_s": setup_spans_s, "driver_host": driver_host,
              "span_memory_gib": span_memory_gib}[spec["reader"]]
    return reader.read(ctx, **spec["args"])


@pytest.mark.parametrize("name,value", [
    ("setup_reference_check_s", 10.0),
    ("setup_init_state_s", 4.0),
    # 5.1-5.5 holds the nested 5.2-5.4 once; the warm-up's 17.8-18.0 counts
    ("setup_trace_lower_s", 0.4 + 0.3 + 0.2),
    # 27.5 less cohort 2, build 0.5, init_state 4, the check 10, warm-up 3
    ("setup_unspanned_s", 27.5 - 19.5 - 1e-6),
    ("hbm_peak_before_window_gib", 6.0),
    ("hbm_peak_rise_in_reference_check_gib", 6.0 - 3.5),
    ("hbm_state_gib", 3.0 - 1.0),
    # rounds of 0.9, 1.0 and 0.95 s less flushes of 0, 0.7 and 0.8
    ("host_busy_ms_per_round", 1e3 * (0.9 + 0.3 + 0.15) / 3),
    # blocks of 2.0, 2.0, 5.0 and 2.1 s
    ("block_max_over_median", 5.0 / 2.05),
    # the host event run starts at 40.0, the first op at 40.1
    ("first_dispatch_ms", 100.0),
])
def test_each_metric_on_the_made_up_run(ctx, name, value):
    assert metric(ctx, name) == pytest.approx(value, rel=1e-9)
    assert ctx["details"]["spans_missing"] == []


def test_the_ten_are_reported_in_every_cell():
    ten = {"setup_reference_check_s", "setup_init_state_s",
           "setup_trace_lower_s", "setup_unspanned_s",
           "hbm_peak_before_window_gib",
           "hbm_peak_rise_in_reference_check_gib", "hbm_state_gib",
           "host_busy_ms_per_round", "block_max_over_median",
           "first_dispatch_ms"}
    manifest = mf.load_manifest(MANIFEST)
    for w in manifest["workloads"]:
        names = {e["name"] for e, _ in mf.load_cell(MANIFEST,
                                                    w["name"]).per_layer}
        assert ten <= names
    for e in manifest["per_layer"]:
        if e["name"] in ten:
            assert e["better"] == "lower" and "workloads" not in e
            assert e["source"] == ("device_trace" if e["name"]
                                   == "first_dispatch_ms"
                                   else "program_counter")


def test_where_set_up_ends_and_what_a_block_is(ctx):
    tree = sp.tree_of(ctx)
    assert sp.setup_end(tree) == pytest.approx(20.5e9)
    assert [s.name for s in sp.setup_roots(tree)] == [
        "import_program", "cohort", "build", "init_state",
        "reference_check", "warmup"]
    window, traced = sp.blocks(tree)
    # the warm-up's run (depth 1) is no block; the last run is the traced one
    assert [b.id for b in window] == [20, 30, 40, 50] and traced.id == 60
    assert [s.id for s in sp.descendants(tree, traced)] == [
        61, 62, 63, 65, 66, 67, 68, 69]


def test_the_account_closes(ctx):
    unspanned = setup_spans_s.read(ctx, rest=True)
    tree, gaps = ctx["details"]["setup_tree"], ctx["details"]["setup_gaps"]
    own = sum(row["self_s"] for row in tree.values())
    assert own == pytest.approx(19.5 + 1e-6, abs=1e-9)     # the depth-0 union
    assert own + unspanned == pytest.approx(SETUP_S, abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(unspanned, abs=1e-9)
    assert gaps["(process start) .. import_program"] == pytest.approx(2.0)
    assert gaps["import_program .. cohort"] == pytest.approx(6.0 - 1e-6)
    row = tree["init_state/snip_mask/compile/trace"]
    assert row["n"] == 2 and row["total_s"] == pytest.approx(0.6)
    assert row["self_s"] == pytest.approx(0.4)     # the union: nested once
    assert tree["init_state/snip_mask"]["self_s"] == pytest.approx(
        2.0 - 0.4 - 0.3 - 1.0)
    assert tree["init_state"]["self_s"] == pytest.approx(4.0 - 1.4 - 2.0)
    assert tree["warmup/run/round/dispatch_round/compile/trace"]["n"] == 1
    assert list(tree)[0] == "reference_check"      # by total, largest first


def test_memory_by_phase_and_a_rise(ctx):
    assert span_memory_gib.read(ctx, "init_state", "hbm_peak_bytes",
                                rise="cohort") == pytest.approx(3.0)
    phases = ctx["details"]["hbm_by_phase"]
    assert [p["name"] for p in phases] == [
        "cohort", "build", "init_state", "reference_check", "warmup",
        "run", "run", "run", "run", "state_check", "run"]
    assert phases[3] == {"name": "reference_check", "in_use": 3 * GIB,
                         "peak": 6 * GIB}
    # the span before the first sampled one carries no sample
    assert span_memory_gib.read(ctx, "cohort", "hbm_peak_bytes",
                                rise="(span before)") is None
    assert ctx["details"]["spans_missing"] == ["import_program"]


def test_the_slow_block_goes_to_the_details(ctx):
    driver_host.read(ctx, "block_max_over_median")
    slow = ctx["details"]["slow_block"]
    assert (slow["blocks"], slow["index"]) == (4, 2)
    assert slow["seconds"] == pytest.approx(5.0)
    assert slow["by_span_s"] == pytest.approx({"round": 4.5, "flush": 3.9})
    assert slow["median_block_by_span_s"] == pytest.approx(
        {"round": 1.9, "flush": 1.3})


def test_idle_gaps_by_program_span_and_the_clock(ctx):
    driver_host.read(ctx, "first_dispatch_ms")
    idle = ctx["details"]["idle_by_span"]
    # 41.25-41.4 and 42.5-42.97 begin under a flush, 42.99-43.1 after the last
    assert idle["by_span_s"] == pytest.approx({"flush": 0.15 + 0.47,
                                               "run": 0.11})
    assert list(idle["by_span_s"]) == ["flush", "run"]
    tr = ctx["trace"]
    assert idle["idle_s"] == pytest.approx(tr.window_s - tr.busy_s)
    assert sum(idle["by_span_s"].values()) == pytest.approx(idle["idle_s"])
    clock = idle["clock"]
    assert clock["unix_ns_of_trace_zero"] == UNIX + round(TRACE_ZERO * 1e9)
    assert clock["max_offset_from_twin_us"] == pytest.approx({"round": 2.0})
    # the breakdown's own naming is the shortest open host event, JAX's
    assert tr.top_gaps(1)[0][0] == "np.asarray_jax.Array_"


def test_a_span_the_run_did_not_record(ctx):
    ctx["tracer"].events[:] = [e for e in events()
                               if e["name"] not in ("init_state", "flush")
                               and e["span_id"] not in (20, 30, 40, 50)]
    assert setup_spans_s.read(ctx, names=["init_state"]) is None
    assert span_memory_gib.read(ctx, "init_state", "hbm_in_use_bytes",
                                rise="build") is None
    assert driver_host.read(ctx, "block_max_over_median") is None
    assert ctx["details"]["spans_missing"] == ["init_state", "run"]
    # rounds without a flush are all the host's own
    assert driver_host.read(ctx, "host_busy_ms") == pytest.approx(950.0)
    with pytest.raises(ValueError):
        driver_host.read(ctx, "nothing")


def test_a_program_without_the_tree_reads_nothing(ctx):
    """The parent commit's tracer: the harness's five spans with neither ids
    nor depth, and no ``origin_unix_ns``."""
    ctx["tracer"].events[:] = [
        {"name": n, "ph": "X", "ts": 1e6 * i, "dur": 1e6}
        for i, n in enumerate(("cohort", "build", "init_state",
                               "reference_check", "warmup"))]
    cell = mf.load_cell(MANIFEST, "alexnet3d_abcd.protocol")
    new = [e["name"] for e, s in cell.per_layer if s["reader"] in (
        "setup_spans_s", "span_memory_gib", "driver_host")]
    assert len(new) == 10
    assert [metric(ctx, name) for name in new] == [None] * 10
    assert set(ctx["details"]) == {"spans_missing"}
    assert len(ctx["details"]["spans_missing"]) >= 5
