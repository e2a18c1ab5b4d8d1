"""Test harness configuration.

The reference simulates multi-node by spawning real processes per test
(tests/unit/common.py:139 DistributedExec). The JAX analog is cheaper and
exercises the same compiled collectives: force the host platform to expose
8 virtual CPU devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)
so every test runs real GSPMD partitioning + collectives on one process.
Must run before jax is imported anywhere.
"""

import os
import sys

def _maybe_reexec_with_affinity_shim(config) -> None:
    """On hosts with fewer cores than virtual devices, XLA CPU's thread
    pool (sized max(cores, devices)) can have every worker blocked in a
    collective rendezvous with no spare to run the partner collective —
    a flaky fatal abort ("Expected 8 threads to join ... only 4
    arrived"). The affinity shim (csrc/hostsim/affinity_shim.c) widens
    the reported CPU count for pool headroom; LD_PRELOAD must be set
    before process start, so re-exec the identical command line once
    with it injected (after releasing pytest's capture fds, or the new
    process writes into the orphaned capture file)."""
    if (sys.platform != "linux"
            or os.environ.get("_DSTPU_AFFINITY_REEXEC") == "1"
            # xdist/execnet workers bootstrap from stdin — re-exec would
            # re-read an already-consumed stream and hang the session
            or os.environ.get("PYTEST_XDIST_WORKER")
            or "-c" in sys.argv[:3]):
        return
    from deepspeed_tpu.utils.hostsim import cpu_sim_env

    env = cpu_sim_env(n_devices=8)  # single policy home for the shim
    if env.get("LD_PRELOAD") == os.environ.get("LD_PRELOAD"):
        return  # big host, shim unavailable, or already loaded
    env["_DSTPU_AFFINITY_REEXEC"] = "1"
    with open("/proc/self/cmdline", "rb") as f:
        argv = [a.decode() for a in f.read().split(b"\0")[:-1]]
    exe = argv[0] if os.path.sep in argv[0] else sys.executable
    cap = config.pluginmanager.getplugin("capturemanager")
    if cap is not None:
        cap.stop_global_capturing()
    os.execve(exe, argv, env)


os.environ["JAX_PLATFORMS"] = "cpu"  # force: tests never touch the real TPU
# hermetic runs: the engines point the persistent compilation cache at
# the checkout (utils/compile_cache.py); the suite and the workers it
# spawns neither read one run's executables in the next nor fill the
# tree with CPU entries
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# the suite's programs are toy-sized and most run once, so most of a whole
# run is the CPU compiler's time and not the programs': JAX's own switch
# (backend optimization level 0, no expensive LLVM passes) takes a fifth
# off it, and the workers and rehearsals a test spawns inherit it. What
# tests/test_tpu_compile.py compiles is read as the chip's compiler leaves
# it: that module switches it back (its ``_compiled_as_for_the_chip``)
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

# flight-recorder dumps (e.g. a deliberately-fired stall watchdog in the
# engine tests) default to ./dstpu_flight — point them at a temp dir so
# test runs never litter the repo; tests asserting on dump paths
# monkeypatch or delete this env var themselves
if "DSTPU_FLIGHT_DIR" not in os.environ:
    import tempfile

    os.environ["DSTPU_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="dstpu_flight_test_")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# the tests run on the 8-device CPU simulator whatever the machine holds
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Tests must not inherit another test's mesh (engine sets a global)."""
    from deepspeed_tpu.parallel import topology

    topology._GLOBAL_MESH = None
    yield
    topology._GLOBAL_MESH = None


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8():
    """8-way fsdp mesh — the common ZeRO test topology."""
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    return build_mesh(TopologyConfig(dp=1, fsdp=8))


@pytest.fixture()
def mesh_2x4():
    """fsdp=2 × tp=4 — the common 2D test topology."""
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    return build_mesh(TopologyConfig(dp=1, fsdp=2, tp=4))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running measured benchmarks (reference "
        "'nightly' marker analog)")
    _maybe_reexec_with_affinity_shim(config)


def pytest_collection_modifyitems(config, items):
    """Tiering (VERDICT r2 #9): tests listed in tests/slow_tests.txt
    (measured >= 15s on the reference single-core CI host; regenerate
    from a --durations=0 run) get the `slow` marker, so
    `pytest -m "not slow"` is a <15-min smoke tier and `make test`
    remains the full suite."""
    listed = set()
    path = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line and not line.startswith("#"):
                    listed.add(line)
    except OSError:
        return
    matched = set()
    for item in items:
        nodeid = item.nodeid.replace("\\", "/")
        if not nodeid.startswith("tests/"):
            nodeid = "tests/" + nodeid
        if nodeid in listed:
            matched.add(nodeid)
            item.add_marker(pytest.mark.slow)
    # a renamed test or changed parametrize id would silently fall out
    # of the slow set and back into the smoke tier — warn so the list
    # can't drift stale (full-collection runs only; -k/path selections
    # legitimately collect a subset)
    stale = listed - matched
    if stale and not (config.getoption("keyword", "")
                      or config.args not in ([], ["tests"], ["tests/"])):
        import warnings

        warnings.warn(
            f"tests/slow_tests.txt has {len(stale)} entries matching no "
            f"collected test (stale after a rename?): "
            f"{sorted(stale)[:3]}...", stacklevel=1)
