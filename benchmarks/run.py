"""One run of one benchmark cell on the attached TPU.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` from ``--seed``, warms it up,
measures for ``--seconds``, checks the results, and prints one JSON object as
the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics from a profiler trace of a few more rounds), ``device`` and, traced,
``breakdown``. Everything else a run learns goes on earlier lines and into
``benchmarks/out/<cell>.json``. Exits 1 without compiling anything where JAX's
default backend is not a TPU, where the cell needs more chips than JAX finds,
or where the program is not beside the benchmark.
"""
import time

_T0 = time.perf_counter()   # process start, for setup_s

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = p.parse_args(argv)

    sys.path.insert(0, REPO)
    try:
        import jax

        import neuroimagedisttraining_tpu  # noqa: F401  (the program)
        from benchmarks.lib import harness
        from neuroimagedisttraining_tpu.utils.compile_cache import (
            configure_compile_cache,
        )
    except ImportError as e:
        print(f"benchmark: cannot import the program or JAX: {e}",
              file=sys.stderr)
        return 1
    if jax.default_backend() != "tpu":
        print(f"benchmark: no TPU: jax.default_backend() is "
              f"{jax.default_backend()!r}; a cell runs on the chip only",
              file=sys.stderr)
        return 1
    cache = configure_compile_cache()
    # the default keeps programs that compile in under a second out of the
    # persistent cache; init_state has several, and every run would pay them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    out = os.path.join(BENCH, "out")
    result, details = harness.run_cell(
        os.path.join(REPO, "BENCHMARK.json"), args.workload, args.seed,
        args.seconds, bool(args.trace), _T0,
        os.path.join(out, "trace", args.workload))
    details["compile_cache_dir"] = cache
    harness.write_details(os.path.join(out, args.workload + ".json"),
                          result, details)
    for key in ("setup", "reference_check", "window", "state_check",
                "round_program_bytes"):
        if key in details:
            print(json.dumps({key: details[key]}), flush=True)
    print(json.dumps(result), flush=True)
    return 0 if result["failed"] == 0 and result["attempted"] else 1


if __name__ == "__main__":
    sys.exit(main())
