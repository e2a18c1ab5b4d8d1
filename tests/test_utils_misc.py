"""OnDevice init scoping, z3 leaf modules, memory breadcrumbs, profiler
annotations (reference: utils/init_on_device.py, utils/z3_leaf_module.py,
see_memory_usage, utils/nvtx.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.runtime import sharding
from deepspeed_tpu.utils import (OnDevice, get_z3_leaf_modules,
                                 instrument_w_profiler, on_device,
                                 see_memory_usage, set_z3_leaf_modules,
                                 span, step_span, unset_z3_leaf_modules)


class TestOnDevice:
    def test_meta_returns_abstract(self):
        model = get_model("tiny")
        with OnDevice(device="meta"):
            params = model.init(jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(params)
        assert leaves and all(
            isinstance(l, jax.ShapeDtypeStruct) for l in leaves)

    def test_default_materializes(self):
        model = get_model("tiny")
        params = model.init(jax.random.PRNGKey(0))
        assert all(isinstance(l, jax.Array) for l in jax.tree.leaves(params))

    def test_cpu_places_on_host(self):
        model = get_model("tiny")
        with on_device(device="cpu"):
            params = model.init(jax.random.PRNGKey(0))
        leaf = jax.tree.leaves(params)[0]
        assert leaf.devices() == {jax.devices("cpu")[0]}

    def test_disabled_and_bad_device(self):
        with pytest.raises(ValueError):
            OnDevice(device="gpu")
        model = get_model("tiny")
        with OnDevice(device="meta", enabled=False):
            params = model.init(jax.random.PRNGKey(0))
        assert isinstance(jax.tree.leaves(params)[0], jax.Array)

    def test_dtype_cast_applies(self):
        model = get_model("tiny")
        with OnDevice(dtype=jnp.bfloat16, device="meta"):
            params = model.init(jax.random.PRNGKey(0))
        floats = [l for l in jax.tree.leaves(params)
                  if jnp.issubdtype(l.dtype, jnp.floating)]
        assert floats and all(l.dtype == jnp.bfloat16 for l in floats)

    def test_context_ignored_inside_jit(self, devices):
        # engines jit their init; the context must not turn traced init
        # into abstract outputs (reference OnDevice wraps eager ctors)
        import deepspeed_tpu as dstpu

        model = get_model("tiny")
        with OnDevice(device="meta"):
            engine, _, _, _ = dstpu.initialize(
                model=model,
                config={"train_micro_batch_size_per_chip": 1,
                        "optimizer": {"type": "adamw",
                                      "params": {"lr": 1e-3}},
                        "zero_optimization": {"stage": 0}})
        assert all(isinstance(l, jax.Array)
                   for l in jax.tree.leaves(engine.params))

    def test_nesting(self):
        with OnDevice(device="meta"):
            with OnDevice(device="device"):
                assert OnDevice.current().device == "device"
            assert OnDevice.current().device == "meta"
        assert OnDevice.current() is None


class TestZ3LeafModules:
    def teardown_method(self):
        unset_z3_leaf_modules()

    def test_marked_paths_lose_data_axes(self, devices):
        from jax.sharding import PartitionSpec as P

        set_z3_leaf_modules("ln1")
        assert "ln1" in get_z3_leaf_modules()
        spec = P(("dp", "fsdp"), "tp")
        stripped = sharding.z3_leaf_spec("['layers']['ln1']['scale']", spec)
        assert stripped == P(None, "tp")
        untouched = sharding.z3_leaf_spec("['layers']['mlp']['wi']", spec)
        assert untouched == spec

    def test_plan_respects_leaf_marks(self, devices):
        from deepspeed_tpu.config import load_config
        from deepspeed_tpu.parallel import topology as topo

        cfg = load_config({"train_micro_batch_size_per_chip": 1,
                           "zero_optimization": {"stage": 3}})
        mesh = topo.build_mesh(topo.TopologyConfig(dp=1, fsdp=-1))
        plan = sharding.make_sharding_plan(cfg, mesh)
        set_z3_leaf_modules("embed")
        tree = {"embed": {"tokens": ("vocab", "embed")},
                "layers": {"wi": ("embed", "mlp")}}
        shardings = plan.param_shardings(tree)
        assert "fsdp" not in str(shardings["embed"]["tokens"].spec)

    def test_unset(self):
        set_z3_leaf_modules(["a", "b"])
        unset_z3_leaf_modules("a")
        assert get_z3_leaf_modules() == ["b"]
        unset_z3_leaf_modules()
        assert get_z3_leaf_modules() == []


class TestMemoryAndAnnotate:
    def test_see_memory_usage_gated(self):
        assert see_memory_usage("quiet") is None  # disabled by default
        out = see_memory_usage("forced", force=True)
        # CPU backends may lack memory_stats: None is fine; must not raise
        assert out is None or "in_use_gb" in out

    def test_instrument_and_ranges(self):
        @instrument_w_profiler
        def f(x):
            return x * 2

        assert float(f(jnp.float32(3))) == 6.0
        with step_span("test-step", 3), span("test-range", uid=7):
            pass
