"""Optimizer construction + mixed-precision master-weight semantics.

Covers the reference's optimizer stack the TPU way:

  * ``get_base_optimizer`` = _configure_basic_optimizer (engine.py:1960):
    name → optax transform. FusedAdam/CPUAdam distinctions disappear —
    XLA fuses the update math (the multi_tensor_apply of
    csrc/adam/multi_tensor_adam.cu is what the compiler does by default).
    Muon (runtime/zero/muon/) maps to optax.contrib.muon, whose
    Newton-Schulz orthogonalization runs sharded under GSPMD — the
    _apply_distributed_muon_update machinery (stage3.py:1537) is implicit.
  * ``MixedPrecisionState`` = BF16_Optimizer semantics
    (runtime/bf16_optimizer.py:37): bf16 compute params + fp32 master
    weights and fp32 optimizer state, updated from fp32-accumulated grads.
    The master tree is sharded per the ZeRO plan (opt rules), which *is*
    ZeRO-1 partitioning.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from deepspeed_tpu.config.config import Config, OptimizerConfig
from deepspeed_tpu.utils.logging import logger

ADAM_ALIASES = {"adam", "fusedadam", "cpuadam"}
ADAMW_ALIASES = {"adamw", "fusedadamw"}


def get_base_optimizer(
    opt_config: Optional[OptimizerConfig],
    lr_schedule: Optional[Callable] = None,
) -> Tuple[optax.GradientTransformation, float]:
    """Name → optax transform (reference engine.py:1960). Returns
    (transform, base_lr)."""
    if opt_config is None:
        opt_config = OptimizerConfig(type="adamw", params={})
    name = opt_config.type.lower().replace("_", "")
    p = dict(opt_config.params or {})
    lr = p.pop("lr", 1e-3)
    lr_arg = lr_schedule if lr_schedule is not None else lr

    betas = p.pop("betas", (0.9, 0.999))
    eps = p.pop("eps", 1e-8)
    weight_decay = p.pop("weight_decay", 0.01 if name in ADAMW_ALIASES else 0.0)
    p.pop("torch_adam", None)
    p.pop("adam_w_mode", None)
    muon_extra = {k: p.pop(k) for k in
                  ("ns_steps", "nesterov", "adam_b1", "adam_b2")
                  if k in p} if name == "muon" else {}
    if p:
        logger.warning(f"optimizer '{opt_config.type}': ignoring params {sorted(p)}")

    if name in ADAMW_ALIASES:
        tx = optax.adamw(lr_arg, b1=betas[0], b2=betas[1], eps=eps,
                         weight_decay=weight_decay)
    elif name in ADAM_ALIASES:
        tx = optax.adam(lr_arg, b1=betas[0], b2=betas[1], eps=eps)
        if weight_decay:
            tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
    elif name in ("sgd", "momentum"):
        tx = optax.sgd(lr_arg, momentum=betas[0] if name == "momentum" else 0.0)
    elif name in ("lion", "fusedlion", "cpulion"):
        tx = optax.lion(lr_arg, b1=betas[0], b2=betas[1],
                        weight_decay=weight_decay)
    elif name in ("adagrad", "cpuadagrad"):
        tx = optax.adagrad(lr_arg, eps=eps)
    elif name in ("lamb", "fusedlamb"):
        tx = optax.lamb(lr_arg, b1=betas[0], b2=betas[1], eps=eps,
                        weight_decay=weight_decay)
    elif name == "adafactor":
        tx = optax.adafactor(lr_arg)
    elif name == "muon":
        # from-scratch NS-orthogonalized momentum (runtime/muon.py):
        # path-aware routing covers the zoo's STACKED [L, ...] layer
        # weights (optax.contrib.muon only treats exactly-2D leaves as
        # matrices) and the NS matmuls run on ZeRO-sharded momentum
        # under GSPMD — the distributed Newton-Schulz of the reference
        # (_apply_distributed_muon_update, stage3.py:1537) without its
        # gather/scatter hooks
        from deepspeed_tpu.runtime.muon import muon as _muon

        tx = _muon(
            lr_arg, beta=betas[0],
            weight_decay=weight_decay,
            ns_steps=int(muon_extra.get("ns_steps", 5)),
            nesterov=bool(muon_extra.get("nesterov", True)),
            adam_b1=muon_extra.get("adam_b1", 0.9),
            adam_b2=muon_extra.get("adam_b2", 0.999),
            adam_eps=eps)
    else:
        raise ValueError(f"unknown optimizer type '{opt_config.type}'")
    return tx, lr


class MixedPrecisionState(NamedTuple):
    """fp32 master weights + inner optax state (BF16_Optimizer analog)."""

    master: Any  # fp32 param tree (ZeRO-sharded per opt rules)
    inner: Any  # optax state (same sharding as master)


def init_mixed_precision(params_fp32, tx: optax.GradientTransformation,
                         shardings=None) -> MixedPrecisionState:
    """fp32 masters + fresh inner state. ``shardings`` (a tree shaped
    like the params) pins every param-shaped leaf of the inner state:
    the moments are zeros with no data dependence on the sharded
    masters, so left alone XLA materializes them whole on every device
    (8 of ZeRO's 12 state bytes per parameter) and the first train step
    compiles twice, once for that layout and once for the sharded one."""
    master = jax.tree.map(lambda p: p.astype(jnp.float32), params_fp32)
    inner = tx.init(master)
    if shardings is not None:
        inner = optax.tree_utils.tree_map_params(
            tx, jax.lax.with_sharding_constraint, inner, shardings)
    return MixedPrecisionState(master=master, inner=inner)


def _add_at(tree, deltas):
    """``tree`` with ``deltas`` (nested dicts down to some of its leaves)
    added to the leaves they name."""
    if isinstance(deltas, dict):
        return {**tree, **{k: _add_at(tree[k], d) for k, d in deltas.items()}}
    return tree + deltas.astype(tree.dtype)


def apply_mixed_precision_update(
    state: MixedPrecisionState,
    grads_fp32,
    tx: optax.GradientTransformation,
    compute_dtype,
    grad_clip: float = 0.0,
    grad_scale: Optional[jax.Array] = None,
    skip: Optional[jax.Array] = None,
    param_deltas: Optional[Dict[str, Any]] = None,
) -> Tuple[Any, MixedPrecisionState, jax.Array]:
    """One optimizer step (reference BF16_Optimizer.step bf16_optimizer.py:303).

    Returns (new compute-dtype params, new state, global grad norm).
    ``grad_scale`` divides grads (loss-scale unscaling); ``skip`` (bool
    scalar) makes the whole update a no-op (overflow step, reference
    fp16/fused_optimizer.py overflow path). ``param_deltas``: nested dicts
    down to some of the master's leaves, added to them after the update
    (state that no gradient moves; skipped with the step).
    """
    if grad_scale is not None:
        grads_fp32 = jax.tree.map(lambda g: g / grad_scale, grads_fp32)

    gnorm = optax.global_norm(grads_fp32)
    if grad_clip and grad_clip > 0:
        scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-6))
        grads_fp32 = jax.tree.map(lambda g: g * scale, grads_fp32)

    updates, new_inner = tx.update(grads_fp32, state.inner, state.master)
    new_master = optax.apply_updates(state.master, updates)
    if param_deltas:
        new_master = _add_at(new_master, param_deltas)

    if skip is not None:
        new_master = jax.tree.map(
            lambda new, old: jnp.where(skip, old, new), new_master, state.master)
        new_inner = jax.tree.map(
            lambda new, old: jnp.where(skip, old, new) if isinstance(new, jax.Array)
            and new.shape == getattr(old, "shape", None) else new,
            new_inner, state.inner)

    new_params = jax.tree.map(lambda m: m.astype(compute_dtype), new_master)
    return new_params, MixedPrecisionState(new_master, new_inner), gnorm
