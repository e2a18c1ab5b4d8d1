from deepspeed_tpu.utils.logging import logger, log_dist  # noqa: F401

# annotate/init_on_device/memory import jax; resolve them lazily (PEP 562)
# so the host-side launcher processes (runner.py, launch.py pre-binding)
# never pay the jax import for `from deepspeed_tpu.utils.logging import ...`
_LAZY = {
    "instrument_w_profiler": "deepspeed_tpu.utils.annotate",
    "span": "deepspeed_tpu.utils.annotate",
    "step_span": "deepspeed_tpu.utils.annotate",
    "OnDevice": "deepspeed_tpu.utils.init_on_device",
    "on_device": "deepspeed_tpu.utils.init_on_device",
    "see_memory_usage": "deepspeed_tpu.utils.memory",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module 'deepspeed_tpu.utils' has no attribute "
                         f"{name!r}")


def set_z3_leaf_modules(patterns):  # reference utils/z3_leaf_module.py
    from deepspeed_tpu.runtime.sharding import set_z3_leaf_modules as _f

    return _f(patterns)


def unset_z3_leaf_modules(patterns=None):
    from deepspeed_tpu.runtime.sharding import unset_z3_leaf_modules as _f

    return _f(patterns)


def get_z3_leaf_modules():
    from deepspeed_tpu.runtime.sharding import get_z3_leaf_modules as _f

    return _f()
