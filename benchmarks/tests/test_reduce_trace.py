"""The trace reduction on a hand-built event list with known answers."""
import pytest

from benchmarks.lib import reduce_trace as rt
from benchmarks.readers import (collective_exposed, device_window,
                                program_ms_per_round, scope_ms_per_round)

LT = "jit(round_fn)/local_train/while"


def hand_built_trace(rounds=1):
    """One device, times in ns. A round program: a 800 ns loop under
    local_train holding two fusions (the backward one without the scope in
    its own op_name, as JAX prints transposed ops) and 100 ns of loop
    overhead; an all-reduce under aggregate that a fusion overlaps by half;
    then 150 ns idle while the host dispatches; then an eval program."""
    ops = [
        rt.Op("while.1", 0, 800, "jit_round_fn", LT),
        rt.Op("fusion.1", 0, 300, "jit_round_fn", LT + "/body/jvp(M)/conv"),
        rt.Op("fusion.2", 300, 700, "jit_round_fn", "transpose(jvp(M))/conv"),
        rt.Op("all-reduce.1", 800, 900, "jit_round_fn",
              "jit(round_fn)/aggregate/dot_general"),
        rt.Op("fusion.3", 850, 950, "jit_round_fn", "jit(round_fn)/scatter"),
        rt.Op("fusion.9", 1100, 1300, "jit_eval_all", "jit(eval_all)/conv"),
    ]
    rt.nest(ops)
    host = [("dispatch_round", 900, 1000), ("run", 0, 1400),
            ("evaluate", 940, 1200)]
    return rt.Trace({"/device:TPU:0": ops}, host, rounds)


def test_interval_arithmetic():
    assert rt.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert rt.total([(0, 10), (2, 3), (20, 25)]) == 15
    assert rt.subtract([(0, 10)], [(2, 3), (5, 12)]) == [[0, 2], [3, 5]]
    assert rt.subtract([(0, 4), (6, 8)], []) == [[0, 4], [6, 8]]


def test_busy_idle_and_window():
    tr = hand_built_trace()
    assert tr.window_s == pytest.approx(1300e-9)
    assert tr.busy_s == pytest.approx(1150e-9)
    assert tr.idle_share() == pytest.approx(150 / 1300)
    ctx = {"trace": tr}
    assert device_window.read(ctx, "busy_ms") == pytest.approx(1150e-6)
    assert device_window.read(ctx, "gap_ms") == pytest.approx(150e-6)
    assert device_window.read(ctx, "idle_pct") == pytest.approx(15000 / 1300)
    with pytest.raises(ValueError):
        device_window.read(ctx, "nothing")


def test_scope_and_program_times():
    ctx = {"trace": hand_built_trace(rounds=2), "details": {}}
    # the loop's own interval covers the backward op that lost its scope
    assert scope_ms_per_round.read(ctx, "local_train") == pytest.approx(400e-6)
    assert scope_ms_per_round.read(ctx, "aggregate") == pytest.approx(50e-6)
    assert scope_ms_per_round.read(ctx, "eval_cache") is None
    assert program_ms_per_round.read(
        ctx, ["jit_eval_all", "jit_eval_merge"]) == pytest.approx(100e-6)
    assert ctx["details"]["program_s_per_round"]["jit_eval_merge"] == 0
    assert program_ms_per_round.read(ctx, ["jit_nothing"]) is None


def test_self_time_and_breakdown():
    tr = hand_built_trace()
    ops = {o.name: o for o in tr.devices["/device:TPU:0"]}
    assert ops["while.1"].self_ns == 100 and not ops["while.1"].leaf
    assert ops["all-reduce.1"].leaf and ops["fusion.3"].leaf
    top = tr.top_ops(3)
    assert [name for name, _ in top] == [
        "jit_round_fn/fusion.2 [transpose(jvp(M))/conv]",
        "jit_round_fn/fusion.1 [jvp(M)/conv]",
        "jit_eval_all/fusion.9 [jit(eval_all)/conv]"]
    assert top[0][1] == pytest.approx(400e-9)
    # the gap began while dispatch_round, the innermost span, was open
    assert tr.top_gaps(5) == [["dispatch_round", pytest.approx(150e-9)]]


def test_exposed_collective():
    tr = hand_built_trace()
    assert tr.exposed_collective_s() == pytest.approx(50e-9)
    assert collective_exposed.read({"trace": tr}) == pytest.approx(
        100 * 50 / 1300)


def test_empty_trace_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        rt.Trace({"/device:TPU:0": []}, [], 1)


def test_op_names_from_compiled_hlo():
    hlo = '''
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %add.7 = f32[8]{0} add(%p, %p), metadata={op_name="jit(round_fn)/aggregate/add"}
}
ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %fusion.547 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(round_fn)/local_train/while/body/mul" stack_frame_id=4}
  %copy.3 = f32[8]{0} copy(%fusion.547)
  ROOT %all-reduce.28 = f32[8]{0} all-reduce(%copy.3), metadata={op_name="jit(round_fn)/aggregate/dot_general"}
}'''
    names = rt.hlo_op_names(hlo)
    assert names["fusion.547"] == "jit(round_fn)/local_train/while/body/mul"
    assert names["all-reduce.28"].endswith("aggregate/dot_general")
    assert "copy.3" not in names and rt.hlo_op_names(None) == {}
    event = "%fusion.547 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
    assert rt.INSTRUCTION.match(event).group(1) == "fusion.547"
    assert rt.INSTRUCTION.match("all-reduce.28").group(1) == "all-reduce.28"
    op = rt.Op("fusion.547", 0, 1, op_name=names["fusion.547"])
    assert rt.in_scope(op, "local_train") and not rt.in_scope(op, "train")


def test_load_reads_planes_lines_and_names(tmp_path, monkeypatch):
    """``load`` on a stand-in for ``ProfileData`` shaped like a v5e trace:
    ops named by their whole HLO line, modules with a run id, one plane per
    chip, and planes that are no device."""
    from types import SimpleNamespace as NS

    import jax.profiler

    def event(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur, stats=[])

    def device(shift):
        return [NS(name="XLA Modules", events=[
                    event("jit_round_fn(7095703419121151439)", shift, 100)]),
                NS(name="XLA Ops", events=[
                    event("%while.2 = (s32[]) while(s32[] %a), body=%b",
                          shift, 80),
                    event("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p)",
                          shift + 10, 50)]),
                NS(name="Async XLA Ops", events=[event("%copy-start", 0, 999)])]

    planes = [NS(name="/device:TPU:0", lines=device(0)),
              NS(name="/device:TPU:1", lines=device(5)),
              NS(name="#Chip0 Misc", lines=[]),
              NS(name="/host:CPU", lines=[NS(name="main", events=[
                  event("dispatch_round", 0, 20), event("instant", 3, 0)])])]
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: NS(planes=planes)))
    tr = rt.load(str(tmp_path), devices=2, rounds=1,
                 op_names={"while.2": "jit(round_fn)/local_train/while"})
    assert sorted(tr.devices) == ["/device:TPU:0", "/device:TPU:1"]
    ops = tr.devices["/device:TPU:1"]
    assert [(o.name, o.program, o.leaf) for o in ops] == [
        ("while.2", "jit_round_fn", False), ("fusion.5", "jit_round_fn", True)]
    assert ops[0].self_ns == 30 and rt.in_scope(ops[0], "local_train")
    assert tr.host == [("dispatch_round", 0, 20)]
    assert tr.busy_s == pytest.approx(80e-9)
    assert len(rt.load(str(tmp_path), devices=1, rounds=1).devices) == 1
