"""The span tree (obs/trace.py): ids and parents, the round handed down, the
clock anchor, memory at depth-0 exits, the exact tree of the round driver,
compile durations as children, and the name list the benchmark reads."""
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from neuroimagedisttraining_tpu.algorithms import FedAvg, SalientGrads
from neuroimagedisttraining_tpu.core.state import HyperParams
from neuroimagedisttraining_tpu.data import make_synthetic_federated
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.obs import compile as obs_compile
from neuroimagedisttraining_tpu.obs import metrics, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracer():
    """An installed tracer without profiler annotations; the process's one
    ``import_program`` event is out of the way before it."""
    trace.set_tracer(trace.Tracer(annotate=False))
    t = trace.Tracer(annotate=False)
    trace.set_tracer(t)
    yield t
    trace.set_tracer(None)


def _paths(t, keep=lambda e: True):
    """``["run", "run/round", ...]`` in order of opening."""
    by_id = {e["span_id"]: e for e in t.events}

    def path(e):
        up = by_id.get(e["parent"])
        return (path(up) + "/" if up else "") + e["name"]

    return [path(e) for e in sorted(t.events, key=lambda e: e["span_id"])
            if keep(e)]


def _algo(cls=FedAvg, **kwargs):
    data = make_synthetic_federated(
        n_clients=4, samples_per_client=8, test_per_client=4,
        sample_shape=(8, 8, 8, 1), uneven=False)
    hp = HyperParams(lr=0.05, lr_decay=1.0, momentum=0.9, weight_decay=0.0,
                     grad_clip=10.0, local_epochs=1, steps_per_epoch=2,
                     batch_size=4)
    return cls(create_model("small3dcnn", num_classes=1), data, hp,
               loss_type="bce", frac=0.5, seed=0, **kwargs)


def test_ids_and_parents_of_nested_sibling_and_recorded_events():
    t = trace.Tracer(annotate=False)
    with t.span("outer"):
        with t.span("first"):
            now = time.perf_counter_ns()
            t.record("measured", now - 500, 500, {"k": 1})
        with t.span("second"):
            pass
    t.record("loose", time.perf_counter_ns(), 0)
    ev = {e["name"]: e for e in t.events}
    ids = [ev[n]["span_id"] for n in ("outer", "first", "measured",
                                      "second", "loose")]
    assert ids == sorted(ids) and len(set(ids)) == 5   # in order of opening
    assert ev["outer"]["parent"] is None and ev["loose"]["parent"] is None
    assert ev["first"]["parent"] == ev["second"]["parent"] \
        == ev["outer"]["span_id"]
    assert ev["measured"]["parent"] == ev["first"]["span_id"]
    assert [ev[n]["args"]["depth"] for n in (
        "outer", "first", "measured", "second", "loose")] == [0, 1, 2, 1, 0]
    assert ev["measured"]["args"]["k"] == 1
    assert ev["measured"]["dur"] == pytest.approx(0.5)
    assert ev["measured"]["ts"] == pytest.approx(
        (now - 500 - t._origin_ns) / 1e3)


def test_a_step_span_hands_its_round_to_everything_under_it():
    t = trace.Tracer(annotate=False)
    with t.span("run"):
        with t.step_span("round", 7):
            with t.span("dispatch_round"):
                with t.span("deeper"):
                    t.record("compile/trace", time.perf_counter_ns(), 1)
            with t.span("flush", {"round": 6}):   # the record's own round
                pass
        with t.span("after"):
            pass
    ev = {e["name"]: e["args"] for e in t.events}
    assert ev["round"]["step"] == 7 and "round" not in ev["round"]
    assert ev["dispatch_round"]["round"] == ev["deeper"]["round"] \
        == ev["compile/trace"]["round"] == 7
    assert ev["flush"]["round"] == 6
    assert "round" not in ev["run"] and "round" not in ev["after"]


def test_to_unix_ns_lies_on_the_profilers_clock(tmp_path):
    """The ``.xplane.pb`` counts its host events from the
    ``profile_start_time`` of its Task Environment plane (unix ns): a span
    put there through ``to_unix_ns`` meets its annotation twin."""
    from jax.profiler import ProfileData

    t = trace.Tracer(annotate=True)
    assert abs(t.origin_unix_ns - time.time_ns()) < 5e9
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with t.span("span_with_a_twin"):
            jnp.ones((64, 64)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    zero, twins = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            zero = dict(plane.stats).get("profile_start_time")
        for line in plane.lines:
            twins += [e.start_ns for e in line.events
                      if e.name == "span_with_a_twin"]
    if zero is None or not twins:
        pytest.skip("this profiler's CPU trace holds no annotation")
    (event,) = t.events
    assert abs(t.to_unix_ns(event) - (zero + twins[0])) < 5e6


def test_the_written_file_holds_the_clock_anchor(tmp_path):
    t = trace.Tracer(annotate=False)
    with t.span("s"):
        pass
    doc = json.load(open(t.write(str(tmp_path / "t.json"))))
    assert doc["origin_unix_ns"] == t.origin_unix_ns
    assert t.to_unix_ns(t.events[0]) == t.origin_unix_ns + round(
        t.events[0]["ts"] * 1e3)


class _Chip:
    def __init__(self, in_use, peak):
        self._stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self._stats


def test_a_depth_0_exit_samples_the_allocator_and_no_other(monkeypatch):
    chips = [_Chip(10, 70), _Chip(30, 50)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    t = trace.Tracer(annotate=False)
    with t.span("phase"):
        with t.span("inner"):
            pass
        t.record("measured", time.perf_counter_ns(), 1)
    t.record("loose", time.perf_counter_ns(), 1)      # depth 0, recorded
    ev = {e["name"]: e["args"] for e in t.events}
    assert ev["phase"]["hbm_in_use_bytes"] == 30     # largest over the chips
    assert ev["phase"]["hbm_peak_bytes"] == 70
    for name in ("inner", "measured", "loose"):
        assert not {"hbm_in_use_bytes", "hbm_peak_bytes"} & set(ev[name])


def test_on_the_cpu_no_event_carries_memory():
    t = trace.Tracer(annotate=False)
    with t.span("phase"):
        pass
    assert set(t.events[0]["args"]) == {"depth"}


def test_the_tree_of_three_rounds_with_eval(tracer):
    algo = _algo()
    state = algo.init_state(jax.random.PRNGKey(0))
    del tracer.events[:]
    algo.run(3, eval_every=1, state=state, finalize=False)
    rnd = ["run/round", "run/round/sample", "run/round/dispatch_round",
           "run/round/evaluate"]
    assert _paths(tracer) == ["run"] + rnd + 2 * (rnd + ["run/round/flush"]) \
        + ["run/flush"]
    ev = sorted(tracer.events, key=lambda e: e["span_id"])
    assert [e["args"]["round"] for e in ev if e["name"] == "flush"] \
        == [0, 1, 2]
    assert [e["args"]["step"] for e in ev if e["name"] == "round"] \
        == [0, 1, 2]
    assert all(e["args"]["round"] == 1 for e in ev[6:9])
    run = ev[0]["args"]
    assert (run["rounds"], run["fuse_rounds"], run["depth"]) == (3, 1, 0)
    # six events a round at most, with the block's one
    assert len(ev) <= 6 * 3


def test_the_tree_of_a_fused_run(tracer):
    algo = _algo()
    state = algo.init_state(jax.random.PRNGKey(0))
    del tracer.events[:]
    algo.run(4, eval_every=0, state=state, finalize=False, fuse_rounds=2)
    paths = _paths(tracer)
    sample = "run/fused_block_dispatch/sample"
    # the first block is fetched once the second is dispatched; a block's
    # client draws are made under its dispatch (the first also draws once
    # to shape the program)
    assert [p for p in paths if p != sample] == [
        "run", "run/fused_block_dispatch", "run/fused_block_dispatch",
        "run/fused_block_flush", "run/fused_block_flush"]
    assert paths.count(sample) >= 4 and paths[-3] == sample
    assert tracer.events[-1]["args"]["fuse_rounds"] == 2


def test_init_state_holds_its_phases(tracer):
    algo = _algo(SalientGrads, dense_ratio=0.5)
    with trace.span("init_state"):
        algo.init_state(jax.random.PRNGKey(0))
    assert _paths(tracer) == ["init_state", "init_state/init_params",
                              "init_state/snip_mask"]


def test_the_state_is_placed_under_a_span_on_a_mesh(tracer):
    from neuroimagedisttraining_tpu.parallel import (
        make_mesh,
        shard_over_clients,
    )

    algo = _algo()
    algo.data = shard_over_clients(algo.data, make_mesh(4))
    state = algo.init_state(jax.random.PRNGKey(0))
    del tracer.events[:]
    algo.place_state(state)
    assert _paths(tracer) == ["place_state"]


def test_with_the_null_tracer_a_run_records_nothing():
    assert trace.get_tracer() is trace.NULL_TRACER
    assert trace.span("a") is trace.span("b") is trace.step_span("c", 1)
    assert trace.NULL_TRACER.record("x", 0, 1) is None
    idle = trace.Tracer(annotate=False)     # made, never installed
    algo = _algo()
    algo.run(2, eval_every=1, finalize=False)
    assert idle.events == [] and not trace.tracing_enabled()


def test_compile_durations_are_children_of_the_open_span(tracer):
    reg = metrics.MetricsRegistry()
    watch = obs_compile.CompileWatch(reg).install()
    inner = jax.jit(lambda x: x * 2 + 1)

    def outer(x):        # traced inside outer's trace: the two nest
        return inner(x) - 3

    try:
        with trace.span("dispatch_round") as sp:
            jax.jit(outer)(jnp.ones((5,)))
    finally:
        watch.uninstall()
    ev = tracer.events
    parent = next(e for e in ev if e["name"] == "dispatch_round")
    compiles = [e for e in ev if e["name"].startswith("compile/")]
    assert {e["name"] for e in compiles} == {
        "compile/trace", "compile/lower", "compile/backend"}
    assert all(e["parent"] == parent["span_id"]
               and e["args"]["depth"] == 1 for e in compiles)
    # the registry's labels read as before: the innermost non-compile span
    for name in ("compile_trace_s", "compile_lower_s", "compile_backend_s"):
        labeled = reg.snapshot()[name]["labeled"]
        assert set(labeled) == {"entry=dispatch_round"}
    # an event ends when its listener fired and lies inside the span
    for e in compiles:
        assert parent["ts"] <= e["ts"] + 1.0
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1.0
    # nested traces: their union is shorter than their sum
    traces = sorted((e["ts"], e["ts"] + e["dur"]) for e in compiles
                    if e["name"] == "compile/trace")
    assert len(traces) >= 2
    union, end = 0.0, float("-inf")
    for a, b in traces:
        union += max(0.0, b - max(a, end))
        end = max(end, b)
    assert union < sum(b - a for a, b in traces)
    assert union <= parent["dur"] + 1.0


def test_import_program_goes_to_the_first_tracer_installed(monkeypatch):
    import neuroimagedisttraining_tpu as program

    monkeypatch.setattr(trace, "_import_recorded", False)
    first, second = trace.Tracer(annotate=False), trace.Tracer(annotate=False)
    try:
        trace.set_tracer(None)          # the null tracer takes nothing
        assert trace._import_recorded is False
        trace.set_tracer(first)
        trace.set_tracer(second)
    finally:
        trace.set_tracer(None)
    (event,) = first.events
    assert second.events == []
    assert event["name"] == "import_program" and event["parent"] is None
    assert event["args"] == {"depth": 0}
    assert event["ts"] < 0      # the program was imported before the tracer
    assert event["ts"] == pytest.approx(
        (program._IMPORT_START_NS - first._origin_ns) / 1e3)
    assert program._IMPORT_END_NS >= program._IMPORT_START_NS


def _asked_spans():
    """Every span name a metric file of the benchmark asks its reader for."""
    from benchmarks.readers import driver_host

    asked = set(driver_host.SPANS)
    for path in glob.glob(os.path.join(REPO, "benchmarks", "metrics",
                                       "*.json")):
        with open(path) as f:
            spec = json.load(f)
        args = spec.get("args", {})
        if spec["reader"] == "setup_spans_s":
            asked |= set(args.get("names", ()))
        if spec["reader"] == "span_memory_gib":
            asked |= {args["at"], args.get("rise")} - {None}
    return sorted(n for n in asked if not n.startswith("("))


@pytest.mark.parametrize("name", _asked_spans())
def test_every_span_a_metric_asks_for_is_in_the_list(name):
    assert len(_asked_spans()) >= 8
    listed = trace.__doc__.split("The span names,")[1]
    assert name in listed.replace(",", " ").replace("``", " ").split()
