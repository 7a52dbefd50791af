"""Are the program's tracer and the profiler on one clock? Run on the chip
after a traced run of ``<cell>``:

    python3 benchmarks/tests/clock_check.py <cell>

The ``driver_host`` reader lays the tracer's spans on the trace by one twin
(the host event ``run``) and writes what unix time that puts the trace's zero
at (``readers.idle_by_span.clock`` of ``benchmarks/out/<cell>.json``: the
tracer's ``origin_unix_ns`` and ``perf_counter_ns`` only). The profiler says
the same thing itself: its ``.xplane.pb`` counts host events from the
``profile_start_time`` of its ``Task Environment`` plane. Prints both, their
difference and the other twins' offsets as one JSON line; exits 1 where the
difference or an offset passes a millisecond."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
LIMIT_US = 1000.0


def main(cell: str) -> int:
    from jax.profiler import ProfileData

    from benchmarks.lib.reduce_trace import newest_xplane

    with open(os.path.join(BENCH, "out", cell + ".json")) as f:
        clock = json.load(f)["readers"]["idle_by_span"]["clock"]
    path = newest_xplane(os.path.join(BENCH, "out", "trace", cell))
    start = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
    if start is None:
        print(f"clock_check: {path} has no profile_start_time",
              file=sys.stderr)
        return 1
    off_us = (clock["unix_ns_of_trace_zero"] - start) / 1e3
    worst = max([abs(off_us)] + list(
        clock["max_offset_from_twin_us"].values()))
    print(json.dumps({"cell": cell, "profile_start_time": start,
                      "unix_ns_of_trace_zero": clock["unix_ns_of_trace_zero"],
                      "tracer_minus_profiler_us": off_us,
                      "max_offset_from_twin_us":
                          clock["max_offset_from_twin_us"],
                      "ok": worst <= LIMIT_US}))
    return 0 if worst <= LIMIT_US else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
