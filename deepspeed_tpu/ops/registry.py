"""Named op registry with compatibility probing.

Reference: op_builder/ (builder.py:116 ``OpBuilder`` ABC with
``is_compatible()``/``load()``; 26 named builders,
``get_accelerator().create_op_builder(name)``). CUDA needs a JIT C++
build step; Pallas/XLA ops are jitted by XLA itself, so the registry's
job reduces to a stable name → op table for tooling (`dstpu-report`
prints the compat column like ds_report) with a probe per op that says
whether a device kernel exists here. Nothing dispatches through it:
``ops/attention.py`` decides which attention runs.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class OpSpec:
    name: str
    description: str
    load: Callable[[], Callable]          # returns the op's callable
    compat_probe: Optional[Callable[[], Tuple[bool, str]]] = None

    def is_compatible(self) -> Tuple[bool, str]:
        if self.compat_probe is None:
            return True, ""
        try:
            return self.compat_probe()
        except Exception as e:  # a probe must never crash tooling
            return False, f"probe error: {e}"


_REGISTRY: Dict[str, OpSpec] = {}


def register_op(name: str, description: str,
                compat_probe: Optional[Callable] = None):
    """Decorator-style registration of a loader function."""

    def deco(load_fn):
        _REGISTRY[name] = OpSpec(name, description, load_fn, compat_probe)
        return load_fn

    return deco


def get_op(name: str) -> Callable:
    _ensure_builtin()
    if name not in _REGISTRY:
        raise KeyError(f"unknown op {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name].load()


def all_ops() -> Dict[str, OpSpec]:
    _ensure_builtin()
    return dict(_REGISTRY)


def _tpu_probe() -> Tuple[bool, str]:
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return True, ""
    # callable for CPU tests, but never a device kernel: say so
    return True, (f"interpret-only: backend={backend} has no device "
                  "kernel (Pallas interpreter, for tests)")


_BUILTIN_LOADED = False


def _ensure_builtin() -> None:
    global _BUILTIN_LOADED
    if _BUILTIN_LOADED:
        return
    _BUILTIN_LOADED = True

    @register_op("flash_attention",
                 "Pallas blockwise flash attention, fwd+bwd custom VJP "
                 "(ref: csrc/transformer fused attention)",
                 _tpu_probe)
    def _load_flash():
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention

    @register_op("quantize_blockwise",
                 "Pallas blockwise INT8/INT4 quantization "
                 "(ref: csrc/quantization/quantize.cu)",
                 _tpu_probe)
    def _load_quant():
        from deepspeed_tpu.ops.pallas.quantization import quantize_blockwise

        return quantize_blockwise

    @register_op("dequantize_blockwise",
                 "Pallas blockwise dequantization "
                 "(ref: csrc/quantization/dequantize.cu)",
                 _tpu_probe)
    def _load_dequant():
        from deepspeed_tpu.ops.pallas.quantization import dequantize_blockwise

        return dequantize_blockwise

    @register_op("xla_attention",
                 "XLA-fused multi-head attention fallback")
    def _load_xla_attn():
        from deepspeed_tpu.ops.attention import xla_attention

        return xla_attention

    @register_op("ragged_forward",
                 "paged-KV ragged inference step "
                 "(ref: inference/v2/kernels/ragged_ops)")
    def _load_ragged():
        from deepspeed_tpu.inference.model_runner import ragged_forward

        return ragged_forward
