"""Test config: run on an 8-device virtual CPU mesh so sharding/collective
paths are exercised without TPU pods (mirrors how the reference tests
multi-node via multi-process on one host, SURVEY.md §4)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The package points jax's persistent compile cache at <checkout>/.jax_cache
# (framework/device.py). The suite — and every worker process it spawns —
# runs with the cache OFF: XLA:CPU's AOT loader writes two multi-KB
# machine-feature lines to stderr on every hit, into output this suite's
# dot lines are counted from, and CPU compiles are not what the cache is for.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the suite is the CPU suite even when an outer shell exported another
# JAX_PLATFORMS before python started
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_everything():
    np.random.seed(0)
    import paddle_tpu as paddle
    paddle.seed(0)
    yield


# --- counting clock: the zero-overhead-when-off test pattern ---------
# One time-module stand-in shared by the telemetry / monitor / cost
# suites (it used to be copy-pasted per file): patch it over the
# modules whose hot paths must not read a clock, serve, assert
# ``fake.calls == 0``.

class CountingTime:
    """time-module stand-in that counts every clock read."""

    def __init__(self):
        self.calls = 0

    def perf_counter(self):
        self.calls += 1
        import time
        return time.perf_counter()

    def monotonic(self):
        self.calls += 1
        import time
        return time.monotonic()

    def thread_time(self):
        self.calls += 1
        import time
        return time.thread_time()


@pytest.fixture
def counting_clock(monkeypatch):
    """CountingTime patched over the serving modules that own hot-path
    clock reads (scheduler + telemetry — monitor/accounting never
    import ``time`` at all, which their tests assert separately)."""
    from paddle_tpu.inference import scheduler as sched_mod
    from paddle_tpu.inference import telemetry as tele_mod
    fake = CountingTime()
    monkeypatch.setattr(sched_mod, "time", fake)
    monkeypatch.setattr(tele_mod, "time", fake)
    return fake


# --- pool invariant auditing (inference/resilience.py) ---------------
# `pytest --audit-invariants` wraps every paged-engine step so
# PagedKVCache/engine bookkeeping is audited after EACH step across
# the paged / prefix / speculative / resilience suites (slower:
# the deep audit fingerprints shared pages; off by default).

def pytest_addoption(parser):
    parser.addoption(
        "--audit-invariants", action="store_true", default=False,
        help="run check_invariants() after every PagedServingEngine/"
             "SpeculativeEngine step (deep pool audit; slow)")


@pytest.fixture(scope="session", autouse=True)
def _audit_invariants(request):
    if not request.config.getoption("--audit-invariants"):
        yield
        return
    from paddle_tpu.inference import (PagedServingEngine,
                                      SpeculativeEngine)
    patched = []

    def wrap(cls, name):
        fn = getattr(cls, name)

        def wrapped(self, *a, **kw):
            # audit only steps that RETURN: an injected EngineCrash
            # abandons the engine mid-mutation by design (recovery
            # rebuilds from snapshot), so torn state is not auditable —
            # and no other exception ever escapes step()/step_multi()
            out = fn(self, *a, **kw)
            self.check_invariants()
            return out
        patched.append((cls, name, fn))
        setattr(cls, name, wrapped)

    wrap(PagedServingEngine, "step")
    wrap(PagedServingEngine, "step_multi")
    wrap(SpeculativeEngine, "step")
    yield
    for cls, name, fn in patched:
        setattr(cls, name, fn)


# --- speculative-decode per-test budget (tools/spec_budget.py) -------
# The spec subsystem's tests drive whole serving loops; an accidental
# blowup there would eat the tier-1 timeout. Any ``spec``-marked test
# (and anything in tests/test_spec*, marker or not) whose CALL phase
# exceeds the budget fails the SESSION with a named report.
_SPEC_DURATIONS = {}
_SPEC_NODEIDS = set()


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("spec") is not None or \
                "/test_spec" in str(item.fspath).replace("\\", "/"):
            _SPEC_NODEIDS.add(item.nodeid)


def pytest_runtest_logreport(report):
    if report.when == "call" and report.nodeid in _SPEC_NODEIDS:
        _SPEC_DURATIONS[report.nodeid] = report.duration


def pytest_sessionfinish(session, exitstatus):
    if not _SPEC_DURATIONS:
        return
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    from tools import spec_budget
    over = spec_budget.check(_SPEC_DURATIONS)
    if over:
        print("\n" + spec_budget.report(over))
        session.exitstatus = 1


# --- benchmark readers that read what the GPT-3 run does not have -----
# tests/benchmark_suite/test_benchmark.py::test_layer_metric_readers hands
# every per-layer metric's reader a run built from the GPT-3 configuration.
# The metrics of a cell with another architecture read things that run
# lacks (expert counters, row lengths, a grouped-GEMM launch): around that
# test, for those metrics, the run is completed with the planted data of
# tests/benchmark_suite/planted_afmoe.py (or planted_latent.py: latent
# widths and row lengths; planted_conv.py: layer kinds and the stored K/V
# row) before the REAL reader reads it. The metrics that read what a span
# carries beside its duration (planted_host_clock.py) read a collector: for
# them a fabricated session that has the fields is planted as the last one.

@pytest.fixture(autouse=True)
def _planted_run_for_the_reader_test(request, monkeypatch):
    call = getattr(request.node, "callspec", None)
    metric = call.params.get("metric") if call else None
    if getattr(request.node, "originalname", "") != \
            "test_layer_metric_readers" or not isinstance(metric, dict):
        return
    from benchmark import cells
    from tests.benchmark_suite import (planted_afmoe, planted_conv,
                                       planted_host_clock, planted_latent)
    if metric["name"] in planted_host_clock.PLANTED_VALUES:
        # these read the last profile session's collector, not the run
        from paddle_tpu.inference import telemetry
        monkeypatch.setattr(telemetry, "_session",
                            planted_host_clock.planted_collector())
        return
    source = next((m for m in (planted_afmoe, planted_latent, planted_conv)
                   if metric["name"] in m.PLANTED_VALUES), None)
    if source is None:
        return
    real = cells.read_layer_metric

    def planted(name, run):
        return real(name, source.plant(run) if run.get("trace") else run)
    monkeypatch.setattr(cells, "read_layer_metric", planted)

