"""Activation checkpointing (rematerialization) subsystem.

Reference: ``deepspeed/runtime/activation_checkpointing/checkpointing.py``
— Megatron-compatible ``checkpoint()`` (:948, ``CheckpointFunction``
:488) with activation *partitioning* across TP ranks
(``partition_activations`` :377), CPU checkpointing (saved activations
moved to host), contiguous buffers, and RNG-state tracking
(``CudaRNGStatesTracker`` :124); configured by ``configure()`` :1029.

TPU mapping (each reference knob → an XLA-native mechanism):

  * checkpoint()                 → ``jax.checkpoint`` (remat): recompute
    in backward instead of saving; policies choose what to keep.
  * partition_activations        → saved residuals carry a sharding
    constraint over the tp axis, so each rank stores 1/tp of every
    checkpointed activation (GSPMD all-gathers on recompute — the same
    gather the reference does by hand).
  * cpu_checkpointing            → offload policies: checkpointed dot
    outputs spill to pinned host memory and stream back in backward.
  * contiguous_memory_optimization → XLA's allocator already packs
    remat buffers; no user-level pooling exists to configure (no-op).
  * RNG tracking                 → JAX RNG is functional: a dropout key
    threaded through the forward is *by construction* replayed bit-
    identically in recompute, which is everything CudaRNGStatesTracker
    exists to guarantee. ``model_parallel_rng`` derives distinct
    per-tp-rank streams (the tracker's other job).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.logging import logger

# remat policy registry (config activation_checkpointing.policy)
POLICIES = {
    # save nothing, recompute all (reference default checkpoint behavior)
    "nothing_saveable": "nothing_saveable",
    # keep matmul outputs (cheap recompute elsewhere, no matmul replay)
    "dots_saveable": "dots_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    # cpu_checkpointing analog: saved dots live in pinned host memory
    "offload_dots_host": "offload_dots_host",
    # disable remat entirely
    "none": "everything",
    "everything": "everything",
    # named selective saves (checkpoint_name annotations in
    # models/transformer.py _layer): trade HBM for skipped recompute of
    # just those projections — the [S,S] score transient is never saved
    "save_qkv_proj": ("names", ("qkv_proj",)),
    "save_attn_out": ("names", ("attn_out",)),
    "save_qkv_attn_out": ("names", ("qkv_proj", "attn_kernel_out",
                                    "attn_out")),
    "save_attn_mlp": ("names", ("qkv_proj", "attn_kernel_out", "attn_out",
                                "mlp_out")),
}

_GLOBAL_CONFIG: dict = {}


def configure(config=None, partition_activations: Optional[bool] = None,
              cpu_checkpointing: Optional[bool] = None,
              contiguous_memory_optimization: Optional[bool] = None,
              number_checkpoints: Optional[int] = None,
              synchronize_checkpoint_boundary: Optional[bool] = None,
              profile: Optional[bool] = None,
              policy: Optional[str] = None):
    """Reference ``configure`` (checkpointing.py:1029): set module-level
    defaults from an ActivationCheckpointingConfig or keyword overrides."""
    global _GLOBAL_CONFIG
    if config is not None:
        _GLOBAL_CONFIG = {
            "partition_activations": getattr(config, "partition_activations",
                                             False),
            "cpu_checkpointing": getattr(config, "cpu_checkpointing", False),
            "policy": getattr(config, "policy", "nothing_saveable"),
        }
        if getattr(config, "contiguous_memory_optimization", False):
            logger.info("activation checkpointing: "
                        "contiguous_memory_optimization is inherent in "
                        "XLA's allocator (no-op)")
    for k, v in [("partition_activations", partition_activations),
                 ("cpu_checkpointing", cpu_checkpointing),
                 ("policy", policy)]:
        if v is not None:
            _GLOBAL_CONFIG[k] = v
    return dict(_GLOBAL_CONFIG)


def is_configured() -> bool:
    return bool(_GLOBAL_CONFIG)


def resolve_policy(name: Optional[str] = None,
                   cpu_checkpointing: bool = False):
    """Policy name → jax.checkpoint policy object (or the sentinels
    None = save-nothing, 'everything' = no remat)."""
    name = name or _GLOBAL_CONFIG.get("policy", "nothing_saveable")
    canonical = POLICIES.get(name)
    if canonical is None:
        raise ValueError(f"unknown activation checkpointing policy "
                         f"'{name}' (choose from {sorted(POLICIES)})")
    if canonical == "everything":
        return "everything"  # remat explicitly disabled: offload n/a
    if isinstance(canonical, tuple) and canonical[0] == "names":
        names = canonical[1]
        if cpu_checkpointing or _GLOBAL_CONFIG.get("cpu_checkpointing"):
            # honor the host-offload request for named saves too
            return jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=list(names),
                offload_src="device", offload_dst="pinned_host")
        return jax.checkpoint_policies.save_only_these_names(*names)
    if cpu_checkpointing or _GLOBAL_CONFIG.get("cpu_checkpointing"):
        canonical = "offload_dots_host"
    if canonical == "nothing_saveable":
        return None
    if canonical == "offload_dots_host":
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
    return getattr(jax.checkpoint_policies, canonical)


def _partition_constraint(x, mesh):
    """Shard a saved activation's trailing (hidden) dim over tp — the
    partition_activations memory saving (checkpointing.py:377)."""
    if not hasattr(x, "ndim") or x.ndim < 1 or mesh is None \
            or mesh.shape.get("tp", 1) == 1:
        return x
    spec = [None] * x.ndim
    if x.shape[-1] % mesh.shape["tp"] == 0:
        spec[-1] = "tp"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def _and_these_names(policy, names: Tuple[str, ...]):
    """``policy`` (None: save nothing) and, saved on the device, the values
    named ``names``. (``save_from_both_policies`` takes two policies that
    answer True or False; an offloading policy answers with a place.)"""
    named = jax.checkpoint_policies.save_only_these_names(*names)
    if policy is None:
        return named

    def either(prim, *args, **params):
        return named(prim, *args, **params) or policy(prim, *args, **params)

    return either


def checkpoint_wrapper(function: Callable,
                       policy: Optional[str] = None,
                       partition_activations: Optional[bool] = None,
                       cpu_checkpointing: bool = False,
                       kept_names: Tuple[str, ...] = ()) -> Callable:
    """Wrap ``function`` with the configured remat behavior.

    ``kept_names`` are ``checkpoint_name`` tags the *model* keeps across
    the checkpoint under every policy, on the device, beside whatever the
    policy saves: results that carry no gradient and cost more to make
    again than to hold (an expert layer's routing integers,
    ``parallel/moe.py::ROUTING_NAME``: kilobytes against a sort). The
    policy keeps deciding about activations; the empty tuple wraps as
    before."""
    resolved = resolve_policy(policy, cpu_checkpointing)
    part = (_GLOBAL_CONFIG.get("partition_activations", False)
            if partition_activations is None else partition_activations)
    if kept_names and resolved != "everything":
        resolved = _and_these_names(resolved, kept_names)

    if resolved == "everything":
        inner = function
    elif resolved is None:
        inner = jax.checkpoint(function)
    else:
        inner = jax.checkpoint(function, policy=resolved)

    if not part:
        return inner

    def wrapped(*args, **kwargs):
        from deepspeed_tpu.parallel import topology

        mesh = topology._GLOBAL_MESH
        # constrain only the first argument — the residual stream whose
        # save is the memory cost; index/aux args must keep their layout
        if args and isinstance(args[0], jax.Array):
            args = (_partition_constraint(args[0], mesh),) + args[1:]
        return inner(*args, **kwargs)

    return wrapped


def checkpoint(function: Callable, *args, **kwargs) -> Any:
    """Reference-parity direct call (checkpointing.py:948): run
    ``function(*args)`` under the configured remat policy."""
    return checkpoint_wrapper(function)(*args, **kwargs)


def model_parallel_rng(key: jax.Array, axis: str = "tp") -> jax.Array:
    """Distinct RNG stream per model-parallel rank (the
    CudaRNGStatesTracker 'model-parallel-rng' stream, checkpointing.py
    :124): fold the axis index into the key. Use inside shard_map; under
    plain GSPMD, dropout on sharded activations is already
    rank-decorrelated by position."""
    return jax.random.fold_in(key, jax.lax.axis_index(axis))
