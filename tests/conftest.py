"""Test configuration: force CPU backend with 8 virtual devices.

Reference parity: the reference runs one test suite against N backends
(platform-tests with nd4j-native vs nd4j-cuda — SURVEY.md §4). Here the
suite runs on the CPU backend with a virtual 8-device mesh so every
sharding/parallelism test exercises real SPMD partitioning without TPU
hardware; the same code paths run unchanged on a real TPU slice.
"""

import os

# Must be set before jax is imported anywhere.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("DL4J_TPU_MATMUL_PRECISION", "float32")

import jax  # noqa: E402

# jax.config trumps the env var: whatever configured jax before this file
# was imported, the suite runs on the 8-device virtual CPU mesh.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "quick: fast smoke tier covering every subsystem "
        "(`pytest -m quick`, target <120s — the CI gate)")
    config.addinivalue_line(
        "markers",
        "races: seeded thread-interleaving tests (`pytest -m races`) — "
        "the InterleavingHarness determinism pins, the instrumented-lock "
        "layer, and one regression per E201/E202 repo fix. Like chaos, "
        "deliberately a fast marker so tier-1's `-m 'not slow'` gate "
        "runs every race schedule")
    config.addinivalue_line(
        "markers",
        "multihost: real multi-OS-process coordination tests "
        "(`pytest -m multihost`) — 2 worker processes rendezvous over "
        "the socket/file CoordinationService (ISSUE 15 tier 3: barrier "
        "agreement, dead-peer detection) or a gloo-backed global mesh. "
        "DELIBERATELY fast (<30 s for the socket tests) and NOT marked "
        "slow, so tier-1's `-m 'not slow'` gate runs the real-process "
        "coordination paths on every run")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection sweeps through the resilience and "
        "elastic layers (`pytest -m chaos`). DELIBERATELY a fast marker, "
        "not a slow one: tier-1 runs `-m 'not slow'`, so every chaos "
        "sweep — including the elastic device-loss/hung-dispatch sweeps — "
        "is part of the default gate")
    config.addinivalue_line(
        "markers",
        "slow: long-running acceptance runs EXCLUDED from tier-1's "
        "`-m 'not slow'` gate (e.g. the ISSUE-17 "
        "`tune resnet50 --budget 20` step-time-reduction pin)")


# ---------------------------------------------------- tier-1 budget report
# The tier-1 gate is `-m 'not slow'` under a 1500 s timeout (ROADMAP).
# This report keeps the headroom visible on every run: total non-slow
# wall time vs the ceiling (warn at 80%) plus the slowest 10 non-slow
# tests — the candidates to optimize or demote to `slow` BEFORE the
# ceiling is hit, not after CI starts flaking on timeout.
TIER1_CEILING_S = 1500.0
TIER1_WARN_FRAC = 0.8
_test_durations: dict = {}
_slow_nodeids: set = set()


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("slow") is not None:
            _slow_nodeids.add(item.nodeid)


def pytest_runtest_logreport(report):
    # sum setup+call+teardown per nodeid
    _test_durations[report.nodeid] = (
        _test_durations.get(report.nodeid, 0.0) + report.duration)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    non_slow = {nid: d for nid, d in _test_durations.items()
                if nid not in _slow_nodeids}
    if not non_slow:
        return
    total = sum(non_slow.values())
    tr = terminalreporter
    tr.section("tier-1 budget")
    pct = 100.0 * total / TIER1_CEILING_S
    tr.write_line(f"non-slow wall time: {total:.1f}s of "
                  f"{TIER1_CEILING_S:.0f}s ceiling ({pct:.0f}%)")
    if total >= TIER1_WARN_FRAC * TIER1_CEILING_S:
        tr.write_line(
            f"WARNING: past {TIER1_WARN_FRAC:.0%} of the tier-1 ceiling "
            "— optimize or demote tests to `slow` (candidates below)")
    for nid, d in sorted(non_slow.items(), key=lambda kv: -kv[1])[:10]:
        tr.write_line(f"  {d:7.2f}s  {nid}")


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture
def place_jax_cache():
    """``place(directory | None)`` points jax's own config at a persistent
    cache directory for one test: what ``utils.environment.
    jax_compile_cache_status()`` reads once jax is imported. jax looked
    at ``JAX_COMPILATION_CACHE_DIR`` at import, so a variable set now
    would place nothing. This process's compiles still write nothing
    there (jax decided at its first compile whether it uses a cache)."""
    before = jax.config.jax_compilation_cache_dir

    def place(directory):
        jax.config.update("jax_compilation_cache_dir",
                          None if directory is None else str(directory))
    yield place
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.fixture(autouse=True)
def _fixed_seed():
    from deeplearning4j_tpu.linalg import factory
    factory.setSeed(12345)
    yield
