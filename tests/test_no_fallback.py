"""Nothing on the measurement paths assumes a chip it cannot see.

The rules under test: a device that is in no peak table is an error, not a
v5e; a benchmark off the TPU refuses to run unless asked for the CPU smoke
by name; ``chip_smoke.py`` never reports success without a TPU; the
compile cache lives where the environment says or at one fixed place in
the checkout; a fleet worker's platform is named by its caller and a tpu
worker owns exactly one chip; an in-process replica owns one device.
"""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from deepspeed_tpu.observability import roofline
from deepspeed_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- peaks: a table with a source, and an error outside it ------------------

@pytest.mark.parametrize("detect", [roofline.detect_peak_tflops,
                                    roofline.detect_hbm_gbps])
def test_unknown_device_kind_raises(detect, monkeypatch):
    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv("BENCH_HBM_GBPS", raising=False)
    for kind in ("cpu", "TPU v9 ultra", ""):
        with pytest.raises(roofline.UnknownDeviceError, match="device_kind"):
            detect(types.SimpleNamespace(device_kind=kind))
    with pytest.raises(roofline.UnknownDeviceError):
        detect(jax.devices()[0])  # the CPU simulator is not a chip


def test_known_kind_and_explicit_override(monkeypatch):
    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv("BENCH_HBM_GBPS", raising=False)
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert roofline.detect_peak_tflops(v5e) == 197.0
    assert roofline.detect_hbm_gbps(v5e) == 819.0
    # a caller that models a chip from the CPU names the number itself
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "123.5")
    monkeypatch.setenv("BENCH_HBM_GBPS", "456")
    cpu = jax.devices()[0]
    assert roofline.detect_peak_tflops(cpu) == 123.5
    assert roofline.detect_hbm_gbps(cpu) == 456.0


def test_autotuner_needs_a_memory_limit():
    """The CPU simulator reports no memory_stats(): the budget is the
    caller's to give, never an assumed 16 GB chip."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner

    with pytest.raises(RuntimeError, match="hbm_budget_bytes"):
        Autotuner._detect_hbm()


# -- benchmarks refuse to time the CPU --------------------------------------

def test_bench_guard_off_the_chip(monkeypatch):
    monkeypatch.delenv(roofline.CPU_SMOKE_ENV, raising=False)
    with pytest.raises(roofline.NoChipError, match="not a TPU"):
        roofline.on_tpu_or_named_cpu_smoke()
    monkeypatch.setenv(roofline.CPU_SMOKE_ENV, "1")
    assert roofline.on_tpu_or_named_cpu_smoke() is False


# -- chip_smoke.py ----------------------------------------------------------

def _run_smoke(cwd, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *argv], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_fails_without_a_tpu():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # and it ran nothing: no phase line precedes the verdict
    assert len(proc.stdout.strip().splitlines()) == 1


def test_chip_smoke_alone_prints_no_result(tmp_path):
    """In a directory with the script and nothing else of the repo it
    fails on the import, with no result line at all."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "deepspeed_tpu" in proc.stderr


# -- compile cache: the environment's directory, else one fixed place -------

@pytest.fixture()
def _cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, _cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # fixed: the same answer every time (the path is part of the key)
    assert compile_cache.enable_compile_cache() == want


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               _cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # jax reads the variable itself at import: nothing is set in code
    assert jax.config.jax_compilation_cache_dir is None


# -- one process per chip, one device per replica ---------------------------

def test_supervisor_platform_comes_from_the_caller(tmp_path):
    from deepspeed_tpu.serving import ReplicaSupervisor

    with pytest.raises(TypeError, match="jax_platform"):
        ReplicaSupervisor(str(tmp_path / "a"))
    with pytest.raises(ValueError, match="cpu|tpu"):
        ReplicaSupervisor(str(tmp_path / "b"), jax_platform="gpu")
    with pytest.raises(ValueError, match="tpu_chips"):
        ReplicaSupervisor(str(tmp_path / "c"), jax_platform="tpu")
    cpu = ReplicaSupervisor(str(tmp_path / "d"), jax_platform="cpu")
    assert cpu._chip_env(0) == {}


def test_tpu_worker_owns_exactly_one_chip(tmp_path):
    from deepspeed_tpu.serving import ReplicaSupervisor

    sup = ReplicaSupervisor(str(tmp_path), jax_platform="tpu",
                            tpu_chips=(2, 3))

    class Live:  # a worker process that has not exited
        def poll(self):
            return None

    envs = []
    for rid in (0, 1):
        envs.append(sup._chip_env(rid))
        sup._procs[rid] = Live()
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == 2
    with pytest.raises(RuntimeError, match="no free chip"):
        sup._chip_env(2)
    # a worker that died gives its chip back to its replacement
    sup._procs[0].poll = lambda: -9
    assert sup._chip_env(2)["TPU_VISIBLE_CHIPS"] == "2"
    # and this process must stay off JAX: no in-process engine for canaries
    with pytest.raises(RuntimeError, match="stays off JAX"):
        sup.compute_canary_chains([[1, 2, 3]])


def test_fleet_replicas_own_one_device_each(devices):
    import jax.numpy as jnp

    from deepspeed_tpu.config.config import RouterConfig
    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.serving import build_fleet

    model = get_model("tiny", dtype=jnp.float32, param_dtype=jnp.float32)
    kw = dict(kv_blocks=16, kv_block_size=8, max_tokens_per_step=16,
              max_seqs_per_step=2, max_blocks_per_seq=4, dtype=jnp.float32,
              params=model.init(jax.random.PRNGKey(0)))

    def owned(router):
        return [list(r.engine.mesh.devices.flat)
                for r in router.replicas.values()]

    router = build_fleet(model, RouterConfig(replicas=3), engine_kw=kw,
                         devices=devices[4:6])
    assert owned(router) == [[devices[4]], [devices[5]], [devices[4]]]
    for r in router.replicas.values():
        leaf = jax.tree.leaves(r.engine.params)[0]
        assert leaf.devices() == set(r.engine.mesh.devices.flat)
    # default: the process's own devices, one per replica, in order
    router = build_fleet(model, RouterConfig(replicas=2), engine_kw=kw)
    assert owned(router) == [[devices[0]], [devices[1]]]
    with pytest.raises(ValueError, match="not both"):
        build_fleet(model, RouterConfig(replicas=1),
                    engine_kw=dict(kw, mesh=router.replicas[0].engine.mesh),
                    devices=devices[:1])
