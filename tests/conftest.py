"""Test configuration: an 8-device virtual CPU mesh, fixed BEFORE jax import.

Per SURVEY.md §4, the reference has no test suite; this repo adds the full
pyramid, with multi-device integration tests simulated via
``--xla_force_host_platform_device_count=8`` on CPU. The one tier that needs
a chip (``-m tpu``: the Pallas kernels compiled through Mosaic) is run as
``JAX_PLATFORMS=tpu python -m pytest -m tpu`` on a TPU host; everywhere else
the platform is the CPU and that tier is deselected.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ON_CPU = os.environ["JAX_PLATFORMS"] == "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after env setup)

if _ON_CPU:
    jax.config.update("jax_platforms", "cpu")  # also if jax was preimported

# Persistent compilation cache: the suite is compile-bound, and repeat runs
# re-trace identical programs. JAX_COMPILATION_CACHE_DIR, when set, is where
# it lives (jax reads the variable itself). Otherwise a home-directory cache
# — not the checkout's .jax_cache: XLA:CPU AOT artifacts are large, and the
# chip tool copies the whole tree — keyed by a CPU-feature fingerprint:
# sandbox hosts rotate, and XLA:CPU AOT artifacts cached on a host with a
# larger feature set (e.g. AMX/AVX-512 extensions) SIGILL when executed on a
# smaller one — observed as "Fatal Python error" interpreter crashes in the
# full-size-volume tests. A host change starts a fresh cache instead of
# loading poisoned kernels.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import hashlib

    def _cpu_fingerprint() -> str:
        try:
            with open("/proc/cpuinfo") as f:
                for line in f:
                    if line.startswith("flags"):
                        return hashlib.sha1(
                            line.encode()).hexdigest()[:12]
        except OSError:
            pass
        import platform

        return hashlib.sha1(
            platform.processor().encode()).hexdigest()[:12]

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(os.path.expanduser("~"), ".cache",
                                   f"nidt_jax_cache_{_cpu_fingerprint()}"))
# every program, not only those over jax's 1 s default: most of the suite's
# compile time is small variants that take 0.1-1 s each, thousands of times
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """The ``tpu`` tier compiles kernels for a chip; on the CPU platform it
    is deselected — whatever ``-m`` says (``-m 'not slow'`` replaces
    pytest.ini's default expression) — rather than collected and skipped."""
    if not _ON_CPU:
        return
    tpu = [i for i in items if i.get_closest_marker("tpu")]
    if tpu:
        config.hook.pytest_deselected(items=tpu)
        items[:] = [i for i in items if not i.get_closest_marker("tpu")]


@pytest.fixture(scope="session")
def eight_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


def pytest_sessionfinish(session, exitstatus):
    """Record completed slow-tier runs in tests/.slow_tier_stamp.json.

    The slow tier holds exactly the tests that prove the big claims
    (full-size volumes, torch convergence A/B, 2-process jax.distributed,
    the real-shape ABCD disk path) but runs rarely on this 1-core host;
    the committed stamp records when it last ran green so that fact is
    auditable instead of folklore."""
    import datetime
    import json

    try:
        if os.environ.get("PYTEST_XDIST_WORKER"):
            return  # per-worker partial counts would corrupt the record
        items = getattr(session, "items", []) or []
        # only count slow tests that actually RAN green (a run where they
        # all skip must not stamp a 'green slow run')
        slow = [i for i in items
                if i.get_closest_marker("slow")
                and i.nodeid in _PASSED_NODEIDS]
        if not slow or exitstatus != 0:
            return
        path = os.path.join(os.path.dirname(__file__),
                            ".slow_tier_stamp.json")
        # high-water record: a partial slow selection must not clobber the
        # record of the most complete green slow run (the stamp's point is
        # "when did the FULL tier last run")
        try:
            with open(path) as f:
                prev = json.load(f)
        except Exception:
            prev = {}
        if len(slow) < int(prev.get("slow_tests_run", 0)):
            return
        stamp = {
            "utc": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "slow_tests_run": len(slow),
            "total_tests_run": len(items),
            "exitstatus": int(exitstatus),
        }
        with open(path, "w") as f:
            json.dump(stamp, f, indent=1)
    except Exception:
        pass  # stamping must never fail a test run


_PASSED_NODEIDS: set = set()


def pytest_runtest_logreport(report):
    # feeds pytest_sessionfinish's slow-tier stamp
    if report.when == "call" and report.passed:
        _PASSED_NODEIDS.add(report.nodeid)
