"""Logical-axis sharding rules: ZeRO stages as sharding declarations.

This module replaces the reference's runtime partitioning machinery —
ZeRO stage 1/2 optimizer partitioning (runtime/zero/stage_1_and_2.py:134),
ZeRO-3 parameter partitioning + fetch coordinator
(runtime/zero/stage3.py:148, partitioned_param_coordinator.py:73), and
AutoTP layer surgery (module_inject/auto_tp.py:194) — with t5x-style
logical-axis annotations compiled by GSPMD:

  * every model parameter carries a tuple of *logical* axis names
    ("embed", "mlp", "heads", ...);
  * a rule table maps logical axes → mesh axes depending on the configured
    ZeRO stage / TP / EP degrees;
  * XLA inserts the all-gathers (ZeRO-3 fetch), reduce-scatters (ZeRO-2
    grad partitioning) and all-reduces (TP) that DeepSpeed performs by hand,
    and its latency-hiding scheduler overlaps them (the prefetch window of
    partitioned_param_coordinator.py:310 for free).

ZeRO stage → sharding plan:

  stage 0: params/grads/opt replicated over data axes.
  stage 1: optimizer state + fp32 master weights shard over ("fsdp",)
           [+ ("dp","fsdp") when hpZ shrinks fsdp — see below].
  stage 2: + gradients shard over fsdp (reduce-scatter instead of
           all-reduce; same comm volume as stage_1_and_2.py:1615).
  stage 3: + parameters shard over fsdp (all-gather on use = stage3.py
           fetch_sub_module; XLA schedules the prefetch).

hpZ (ZeRO++ hierarchical partition, partition_parameters.py:1806): set
``zero_hpz_partition_size=k`` → mesh fsdp=k (intra-slice, ICI), dp=N/k
(inter-slice, DCN). Params shard only over fsdp (gathers stay on ICI);
optimizer state shards over ("dp","fsdp") so state is still split N ways.
MiCS (runtime/zero/mics.py) is the same construction with the shard group
chosen by ``mics_shard_size``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.config.config import Config
from deepspeed_tpu.utils.logging import warning_once

# Logical axis vocabulary used by the model zoo (models/layers.py).
LOGICAL_AXES = (
    "batch", "seq", "embed", "mlp", "heads", "kv_heads", "head_dim",
    "vocab", "layers", "expert", "norm", "stack",
)

# Tensor-parallel rule table (AutoTP analog): column-parallel dims.
TP_RULES: Tuple[Tuple[str, Any], ...] = (
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("mlp", "tp"),
    ("vocab", "tp"),
)

# Fully-sharded-data-parallel rule: shard the embed (d_model) dim.
FSDP_RULES: Tuple[Tuple[str, Any], ...] = (("embed", "fsdp"),)

# Expert parallel: experts shard over ep.
EP_RULES: Tuple[Tuple[str, Any], ...] = (("expert", "ep"),)

# Pipeline: the stacked-layer dim shards over pp (GSPMD spatial pipeline).
PP_RULES: Tuple[Tuple[str, Any], ...] = (("layers", "pp"),)

# Activation rules.
ACT_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", ("dp", "fsdp", "ep")),
    ("seq", "sp"),
)


def spec_from_logical(
    logical_axes: Sequence[Optional[str]],
    rules: Sequence[Tuple[str, Any]],
) -> PartitionSpec:
    """Map a tuple of logical axis names to a PartitionSpec.

    First matching rule wins per dim; a mesh axis already used by an earlier
    dim is skipped (GSPMD forbids reuse within one spec).
    """
    used: set = set()
    out = []
    for name in logical_axes:
        entry: Any = None
        if name is not None:
            for lname, maxes in rules:
                if lname != name:
                    continue
                cand = (maxes,) if isinstance(maxes, str) else tuple(maxes)
                cand = tuple(a for a in cand if a not in used)
                if cand:
                    entry = cand[0] if len(cand) == 1 else cand
                    used.update(cand)
                break
        out.append(entry)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Per-role rule tables for one (config, mesh) pair."""

    mesh: Mesh
    param_rules: Tuple[Tuple[str, Any], ...]
    grad_rules: Tuple[Tuple[str, Any], ...]
    opt_rules: Tuple[Tuple[str, Any], ...]
    act_rules: Tuple[Tuple[str, Any], ...] = ACT_RULES

    def param_spec(self, logical_axes) -> PartitionSpec:
        return spec_from_logical(logical_axes, self.param_rules)

    def grad_spec(self, logical_axes) -> PartitionSpec:
        return spec_from_logical(logical_axes, self.grad_rules)

    def opt_spec(self, logical_axes) -> PartitionSpec:
        return spec_from_logical(logical_axes, self.opt_rules)

    # tree-level helpers ----------------------------------------------------
    def param_shardings(self, spec_tree):
        from jax.tree_util import keystr, tree_map_with_path

        # z3-leaf-marked paths keep params replicated over data axes
        # (grad/opt shardings are unaffected, like the reference where
        # leaf modules change fetch behavior, not partitioning of state)
        return tree_map_with_path(
            lambda kp, ax: NamedSharding(
                self.mesh, z3_leaf_spec(keystr(kp), self.param_spec(ax))),
            spec_tree,
            is_leaf=_is_axes_leaf,
        )

    def grad_shardings(self, spec_tree):
        return jax.tree.map(
            lambda ax: NamedSharding(self.mesh, self.grad_spec(ax)),
            spec_tree,
            is_leaf=_is_axes_leaf,
        )

    def opt_shardings(self, spec_tree):
        return jax.tree.map(
            lambda ax: NamedSharding(self.mesh, self.opt_spec(ax)),
            spec_tree,
            is_leaf=_is_axes_leaf,
        )


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)


# ---------------------------------------------------------------------------
# z3 leaf modules (reference deepspeed/utils/z3_leaf_module.py:149)
# ---------------------------------------------------------------------------
# The reference marks modules whose params must be fetched/released as one
# unit instead of per-submodule (granularity control for the ZeRO-3
# coordinator). The GSPMD analog: params under a marked subtree are kept
# REPLICATED over the data axes (dp/fsdp) instead of fully sharded — the
# "always resident as a unit" behavior — while tp/ep sharding still
# applies. Patterns are substrings of the param path (jax.tree keystr).

_Z3_LEAF_PATTERNS: list = []
_DATA_AXES = ("dp", "fsdp")


def set_z3_leaf_modules(patterns) -> list:
    """Mark param-path substrings as leaf units (reference
    set_z3_leaf_modules takes module classes; paths are the tree-world
    handle). Returns the active pattern list."""
    if isinstance(patterns, str):
        patterns = [patterns]
    for p in patterns:
        if p not in _Z3_LEAF_PATTERNS:
            _Z3_LEAF_PATTERNS.append(p)
    return list(_Z3_LEAF_PATTERNS)


def unset_z3_leaf_modules(patterns=None) -> list:
    if patterns is None:
        _Z3_LEAF_PATTERNS.clear()
    else:
        for p in ([patterns] if isinstance(patterns, str) else patterns):
            if p in _Z3_LEAF_PATTERNS:
                _Z3_LEAF_PATTERNS.remove(p)
    return list(_Z3_LEAF_PATTERNS)


def get_z3_leaf_modules() -> list:
    return list(_Z3_LEAF_PATTERNS)


def z3_leaf_spec(path: str, spec: PartitionSpec) -> PartitionSpec:
    """Strip data axes from a spec when ``path`` matches a leaf pattern."""
    if not _Z3_LEAF_PATTERNS or not any(p in path for p in _Z3_LEAF_PATTERNS):
        return spec
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, str):
            out.append(None if entry in _DATA_AXES else entry)
        else:
            kept = tuple(a for a in entry if a not in _DATA_AXES)
            out.append(kept[0] if len(kept) == 1 else (kept or None))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def make_sharding_plan(config: Config, mesh: Mesh) -> ShardingPlan:
    """Compile the config's ZeRO/TP/EP choices into rule tables."""
    stage = config.zero_optimization.stage

    base: list = list(TP_RULES) + list(EP_RULES) + list(PP_RULES)

    param_rules = list(base)
    if stage >= 3:
        param_rules += list(FSDP_RULES)

    grad_rules = list(base)
    if stage >= 2:
        grad_rules += list(FSDP_RULES)

    # Optimizer state / fp32 master weights: stage >= 1 shards over fsdp;
    # with hpZ (dp axis > 1 while fsdp carries the intra-slice shard) the
    # state additionally shards over dp so it is still split N ways.
    opt_rules = list(base)
    if stage >= 1:
        if mesh.shape["dp"] > 1 and config.zero_optimization.zero_hpz_partition_size > 1:
            opt_rules += [("embed", ("dp", "fsdp"))]
        else:
            opt_rules += list(FSDP_RULES)

    if stage >= 1 and mesh.shape["fsdp"] == 1 and mesh.shape["dp"] > 1:
        warning_once(
            "ZeRO stage >= 1 configured but mesh fsdp axis is 1; state will "
            "not shard. Put your data-parallel degree on the fsdp axis "
            "(TopologyConfig(fsdp=-1)) to enable partitioning."
        )

    return ShardingPlan(
        mesh=mesh,
        param_rules=tuple(param_rules),
        grad_rules=tuple(grad_rules),
        opt_rules=tuple(opt_rules),
    )


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


# trace-time switch: inside partial-manual shard_map regions (the pp
# pipeline) sharding constraints on auto axes trip an XLA SPMD bug
# ("Invalid binary instruction opcode copy"); the pipeline disables them
# while tracing its body — batch/tp shardings still propagate from inputs.
_CONSTRAINTS_DISABLED = False
_FORCE_F32 = False


class disable_constraints:
    def __enter__(self):
        global _CONSTRAINTS_DISABLED
        self._prev = _CONSTRAINTS_DISABLED
        _CONSTRAINTS_DISABLED = True

    def __exit__(self, *a):
        global _CONSTRAINTS_DISABLED
        _CONSTRAINTS_DISABLED = self._prev
        return False


_MANUAL_AXES: frozenset = frozenset()


class manual_axes:
    """Trace-scoped marker for partial-manual shard_map regions: the
    named axes are MANUAL inside (not addressable by
    with_sharding_constraint), so activation constraints strip them while
    the auto axes (tp/sp) stay live. Contrast disable_constraints, which
    kills everything — needed only where the XLA bug above applies."""

    def __init__(self, axes):
        self._axes = frozenset(axes)

    def __enter__(self):
        global _MANUAL_AXES
        self._prev = _MANUAL_AXES
        _MANUAL_AXES = _MANUAL_AXES | self._axes

    def __exit__(self, *a):
        global _MANUAL_AXES
        _MANUAL_AXES = self._prev
        return False


_VMAPPED_AXES: frozenset = frozenset()


class vmapped_axes:
    """Trace-scoped marker for explicit per-shard-group vmaps (the qgZ
    per-group gradient construction, engine.py): the named mesh axes are
    carried by the vmapped group dimension, so activation constraints
    inside the mapped trace must not re-pin body dims to them — the
    conflicting pair trips XLA's SPMD grouped-sharding CHECK
    (spmd_partitioner_util.cc num_groups mismatch) once another axis
    (sp) is in play. Unlike manual_axes this strips ONLY activation
    constraints; the qwZ parameter-fetch constraints keep fsdp (params
    are not vmapped)."""

    def __init__(self, axes):
        self._axes = frozenset(axes)

    def __enter__(self):
        global _VMAPPED_AXES
        self._prev = _VMAPPED_AXES
        _VMAPPED_AXES = _VMAPPED_AXES | self._axes

    def __exit__(self, *a):
        global _VMAPPED_AXES
        _VMAPPED_AXES = self._prev
        return False


def _strip_axes_spec(spec, axes) -> PartitionSpec:
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, str):
            out.append(None if e in axes else e)
        else:
            kept = tuple(a for a in e if a not in axes)
            out.append(kept[0] if len(kept) == 1 else (kept or None))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


class force_f32:
    """Trace-time override: model bodies compute in f32 (CPU shard_map
    bf16 workaround — see parallel/pipeline.py)."""

    def __enter__(self):
        global _FORCE_F32
        self._prev = _FORCE_F32
        _FORCE_F32 = True

    def __exit__(self, *a):
        global _FORCE_F32
        _FORCE_F32 = self._prev
        return False


def effective_dtype(requested):
    import jax.numpy as jnp

    return jnp.float32 if _FORCE_F32 else requested


# ---------------------------------------------------------------------------
# ZeRO++ qwZ for stage 3: quantized parameter all-gather
# ---------------------------------------------------------------------------
# Reference: the stage-3 fetch path gathers INT8-quantized parameters
# (partition_parameters.py:1446 ``all_gather_coalesced`` with
# quantization, kernels csrc/quantization/swizzled_quantize.cu),
# halving all-gather wire volume vs fp16/bf16.
#
# GSPMD expression: inside the train step, each fsdp-sharded weight is
# blockwise int8-quantized *on its shard* (local op), the int8 payload +
# scales are forced through the fsdp gather by a pair of sharding
# constraints (sharded → fsdp-stripped), and dequantized after. XLA's
# latency-hiding scheduler still prefetches per layer inside the scan,
# and with hpZ meshes the gather stays intra-fsdp-group by construction.
# Backward is straight-through: grads flow as if the bf16 weight had
# been used directly (matching the reference, which quantizes only the
# gather wire, not the backward).

_QWZ_BITS: Optional[int] = None
QWZ_BLOCK = 128


def configure_qwz(bits: Optional[int]) -> None:
    """Arm/disarm the quantized stage-3 fetch for model code traced
    while armed. Engines arm it only around their own traces (via
    qwz_context) so two engines in one process can't contaminate each
    other's programs."""
    global _QWZ_BITS
    if bits is not None and bits != 8:
        raise ValueError(f"qwZ stage-3 fetch supports int8 only, got {bits}")
    _QWZ_BITS = bits


class qwz_context:
    """Trace-scoped qwZ arming: ``with qwz_context(8): model.loss(...)``."""

    def __init__(self, bits: Optional[int]):
        self._bits = bits

    def __enter__(self):
        global _QWZ_BITS
        self._prev = _QWZ_BITS
        configure_qwz(self._bits)

    def __exit__(self, *a):
        global _QWZ_BITS
        _QWZ_BITS = self._prev
        return False


def qwz_active() -> bool:
    return _QWZ_BITS is not None


def _has_fsdp(entry) -> bool:
    return entry == "fsdp" or (isinstance(entry, tuple) and "fsdp" in entry)


def _strip_fsdp(entries):
    out = []
    for e in entries:
        if e is None or e == "fsdp":
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a != "fsdp")
            out.append(kept[0] if len(kept) == 1 else (kept or None))
        else:
            out.append(e)
    return out


def _straight_through(fn):
    f = jax.custom_vjp(fn)
    f.defvjp(lambda p: (fn(p), None), lambda _, ct: (ct,))
    return f


def quantized_param_fetch(x, logical_axes: Sequence[Optional[str]],
                          path: str = ""):
    """qwZ stage-3 fetch of one weight: int8 all-gather over fsdp.

    No-op unless a qwz_context is armed, a multi-device mesh with
    fsdp > 1 is active, and the weight actually shards over fsdp with at
    least one non-fsdp dim to carry the quantization blocks (1-D norm
    scales/biases stay on the exact bf16 gather — negligible bytes).
    ``path`` lets z3-leaf-marked params (kept replicated by the plan)
    opt out — they have no fsdp gather to quantize.
    """
    import math

    from jax import numpy as jnp

    from deepspeed_tpu.parallel import topology

    mesh = topology._GLOBAL_MESH
    if (_QWZ_BITS is None or _CONSTRAINTS_DISABLED or mesh is None
            or mesh.shape.get("fsdp", 1) <= 1):
        return x
    rules = TP_RULES + EP_RULES + PP_RULES + FSDP_RULES  # stage-3 params
    spec = z3_leaf_spec(path, spec_from_logical(logical_axes, rules))
    if _MANUAL_AXES:
        # inside a partial-manual region (pipeline stages: pp) the fetch
        # constraints may only name auto axes
        spec = _strip_axes_spec(spec, _MANUAL_AXES)
    entries = list(spec) + [None] * (len(x.shape) - len(spec))
    if not any(_has_fsdp(e) for e in entries):
        return x  # not fsdp-partitioned: nothing to win
    candidates = [i for i, e in enumerate(entries) if not _has_fsdp(e)]
    if not candidates:
        return x
    unsharded = [i for i in candidates if entries[i] is None]
    ax = unsharded[-1] if unsharded else candidates[-1]
    n = x.shape[ax]
    # blocks must tile evenly within the chosen dim's own sharding
    div = 1
    if entries[ax] is not None:
        axes_ = (entries[ax],) if isinstance(entries[ax], str) \
            else tuple(entries[ax])
        for a in axes_:
            div *= mesh.shape.get(a, 1)
    if n % max(div, 1) != 0:
        return x
    block = math.gcd(n // max(div, 1), QWZ_BLOCK)
    if block <= 1:
        return x

    spec_blocked = PartitionSpec(
        *(entries[:ax] + [entries[ax], None] + entries[ax + 1:]))
    spec_gathered = PartitionSpec(
        *_strip_fsdp(entries[:ax] + [entries[ax], None] + entries[ax + 1:]))
    sh_blocked = NamedSharding(mesh, spec_blocked)
    sh_gathered = NamedSharding(mesh, spec_gathered)
    shape = x.shape
    blocked_shape = shape[:ax] + (n // block, block) + shape[ax + 1:]

    def qdq(p):
        from deepspeed_tpu.comm import comm as _comm

        f = p.reshape(blocked_shape).astype(jnp.float32)
        s = jnp.max(jnp.abs(f), axis=ax + 1, keepdims=True) / 127.0
        s = jnp.where(s == 0.0, 1.0, s)
        # scales: compute on the shard, gather (tiny fp32), then re-slice
        # the local part for the quantize step. The re-slice makes the
        # int8 gather data-depend on the scales gather, serializing the
        # pair — XLA CPU's in-process communicator deadlocks on too many
        # concurrent all-gathers, and one-outstanding-per-weight is also
        # the right schedule on TPU (scales ride along, payload follows).
        # Both gathers ride comm.traced_span so the flight ring and
        # Perfetto comm lanes account WIRE bytes (int8 payload + fp32
        # scales), not the logical bf16 tensor.
        s = jax.lax.with_sharding_constraint(s, sh_blocked)
        with _comm.traced_span("all_gather", s, "fsdp", "qwz_scales"):
            s_g = jax.lax.with_sharding_constraint(s, sh_gathered)
        s_local = jax.lax.with_sharding_constraint(s_g, sh_blocked)
        q = jnp.round(f / s_local).astype(jnp.int8)
        # quantize on the shard, gather the int8 payload over fsdp
        q = jax.lax.with_sharding_constraint(q, sh_blocked)
        with _comm.traced_span("all_gather", q, "fsdp",
                               "qwz_param_fetch"):
            q = jax.lax.with_sharding_constraint(q, sh_gathered)
        return (q.astype(jnp.float32) * s_g).reshape(shape).astype(p.dtype)

    return _straight_through(qdq)(x)


def qwz_sequence_barrier(weight, value):
    """Schedule a qwZ fetch of ``weight`` after ``value`` is computed.

    Identity for both operands. On the single-process CPU simulator the
    in-process communicator deadlocks when too many all-gathers block
    concurrently (8 virtual devices share one core's thread pool), so
    independent fetches are chained behind the computation that precedes
    them. On TPU the barrier is skipped — overlapping the gather with
    upstream compute is exactly what the latency-hiding scheduler should
    do."""
    if _QWZ_BITS is None or jax.default_backend() == "tpu":
        return weight, value
    return jax.lax.optimization_barrier((weight, value))


def vocab_parallel_lookup(table, ids, axis: str = "tp"):
    """Embedding lookup on a vocab-sharded table without GSPMD's
    replicate-then-partition fallback.

    A plain ``table[ids]`` gathers along the tp-sharded vocab dim; XLA's
    SPMD partitioner handles that by all-gathering the FULL table to every
    device first ("SPMD will replicate the tensor and then partition it"
    — the warning the round-2 multichip dryrun logged). At 128k vocab ×
    8k hidden that is a 2 GB per-step gather that scales with vocab.

    TPU-first construction (reference bar: the vocab/column-parallel
    embedding in module_inject/layers.py:678): a shard_map manual ONLY
    over the vocab axis — each shard masks ids to its own vocab range,
    gathers locally, zeroes out-of-range rows, and a psum over ``axis``
    assembles the row each token actually hit. Wire cost: one [*, H]
    activation psum (the same volume any tp row-parallel matmul pays)
    instead of a [V, H] table gather. The backward is the mirrored
    masked scatter-add into the LOCAL shard — no replicated-table grad.

    Falls back to the plain gather when no mesh is set, the axis is
    unsharded, vocab doesn't tile, or tracing happens inside a manual
    region (pipeline / 1-bit / zeropp shard_maps).
    """
    from deepspeed_tpu.parallel import topology

    mesh = topology._GLOBAL_MESH
    k = 1 if mesh is None else mesh.shape.get(axis, 1)
    V = table.shape[0]
    if _CONSTRAINTS_DISABLED or _MANUAL_AXES or k <= 1 or V % k != 0:
        return table[ids]  # (nested shard_map in a manual region: no)
    import jax.numpy as jnp
    from jax import lax

    shard = V // k
    # XLA's CPU backend miscompiles bf16 inside partial-manual shard_map
    # regions ("Invalid binary instruction opcode copy" — see
    # parallel/pipeline.py); the lookup is exact row selection, so an f32
    # round-trip on the simulator changes nothing numerically.
    cast = (jax.default_backend() == "cpu" and table.dtype == jnp.bfloat16)
    out_dtype = table.dtype
    if cast:
        table = table.astype(jnp.float32)

    def body(tbl, tok):
        # XLA SPMD-partitioner CHECK workaround (spmd_partitioner_util.cc
        # ExpandDeviceGroupsWithIota): a gather whose operand stays
        # auto-sharded over fsdp inside this partial-manual (tp) region
        # crashes the partitioner on pp×fsdp×tp meshes (the 70B class).
        # Fetch the embed dim up front there — at stage 3 this is
        # exactly the ZeRO-3 all-gather of the local vocab shard the
        # lookup needs anyway. Scoped to meshes WITH a pp axis: on
        # pp-free fsdp×tp meshes the gather partitions fine, and the
        # unconditional fetch would add an fsdp all-gather of the table
        # shard per forward where none is needed.
        if mesh.shape.get("fsdp", 1) > 1 and mesh.shape.get("pp", 1) > 1:
            tbl = jax.lax.with_sharding_constraint(
                tbl, NamedSharding(mesh, PartitionSpec(*([None] * tbl.ndim))))
        start = lax.axis_index(axis) * shard
        local = tok - start
        valid = (local >= 0) & (local < shard)
        rows = tbl[jnp.where(valid, local, 0)]
        rows = rows * valid[..., None].astype(tbl.dtype)
        return lax.psum(rows, axis)

    # clamp like XLA's gather does, so out-of-range ids embed to the same
    # row with or without tp instead of silently zeroing under tp
    ids = jnp.clip(ids, 0, V - 1)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(axis), PartitionSpec()),
        out_specs=PartitionSpec(),
        axis_names=frozenset({axis}),
        check_vma=False,
    )(table, ids)
    return out.astype(out_dtype) if cast else out


# ---------------------------------------------------------------------------
# Stage-3 per-layer overlap engine hooks (PR 6)
# ---------------------------------------------------------------------------
# The ZeRO-Infinity path streams layers host->device; the stage-3 path
# has the same shape of problem one tier up: fsdp-sharded resident layer
# stacks whose per-layer all-gather XLA schedules however it likes.
# These two hooks plug the fsdp gather / grad reduce-scatter into
# runtime/param_stream.py::streamed_layers_prefetch as its ``fetch`` /
# ``grad_sink``, so the SAME staged-carry overlap engine (pin_stage
# optimization barriers) sequences per-layer collectives: layer i+k's
# all-gather issues while layer i computes, and layer i's gradient
# reduce-scatter issues inside the backward scan where it overlaps layer
# i-1's recompute. Reference: the reference's stage-3 prefetch +
# reduce-scatter-inside-backward (partition_parameters.py fetch on
# pre-forward, stage3.py reduce_scatter hooks), and T3's fused
# track-and-trigger overlap (PAPERS.md).


def gathered_layer_spec(logical_axes: Sequence[Optional[str]]
                        ) -> PartitionSpec:
    """Spec of ONE layer's weight after the stage-3 fsdp gather: the
    full param rules minus fsdp (tp/ep stay sharded — only the ZeRO
    partition is gathered, matching the reference's stage-3 fetch)."""
    rules = TP_RULES + EP_RULES + PP_RULES + FSDP_RULES
    spec = spec_from_logical(logical_axes, rules)
    return PartitionSpec(*_strip_fsdp(list(spec)))


def _walk_with_logical(params, logical, fn, path=""):
    # logical_axes leaves are TUPLES of axis names, so jax.tree.map
    # would descend into them; walk the dict tree by hand (same pattern
    # as models/transformer.py::_qwz_fetch_tree)
    if isinstance(logical, tuple):
        return fn(params, logical, path)
    return {k: _walk_with_logical(params[k], logical[k], fn,
                                  f"{path}['{k}']")
            for k in params}


def fsdp_gather_slice(stacked_tree: Any, i, logical_tree: Any) -> Any:
    """``fetch`` hook for the overlap engine on the stage-3 path: slice
    layer ``i`` out of the fsdp-sharded resident ``[L, ...]`` stack and
    constrain it to the fsdp-GATHERED spec, so GSPMD emits that layer's
    all-gather at the point in the staged scan where the engine issues
    it. ``logical_tree`` is ``logical_axes(cfg)["layers"]`` (each leaf a
    tuple starting with "layers", dropped for the per-layer slice).

    Falls back to a plain dynamic slice (gather left to GSPMD's default
    placement) when no mesh / fsdp==1 / constraints disabled / inside a
    manual region.
    """
    from jax import lax

    from deepspeed_tpu.parallel import topology

    mesh = topology._GLOBAL_MESH
    passthrough = (_CONSTRAINTS_DISABLED or mesh is None
                   or mesh.shape.get("fsdp", 1) <= 1)

    def slice_one(stack, axes, path):
        sl = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
            stack)
        if passthrough:
            return sl
        spec = gathered_layer_spec(axes[1:])  # drop the "layers" dim
        if _MANUAL_AXES:
            spec = _strip_axes_spec(spec, _MANUAL_AXES)
        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, spec)), sl)

    return _walk_with_logical(stacked_tree, logical_tree, slice_one)


def fsdp_scatter_grads(grads: Any, logical_tree: Any) -> Any:
    """``grad_sink`` hook for the overlap engine on the stage-3 path:
    constrain one layer's parameter cotangent back to the fsdp-SHARDED
    spec inside the backward scan, so GSPMD emits the per-layer gradient
    reduce-scatter right there — overlapping the previous layer's
    recompute instead of coalescing at the scan epilogue. This is the
    GSPMD expression of the reference's reduce-scatter-inside-backward
    (stage3.py gradient hooks)."""
    from deepspeed_tpu.parallel import topology

    mesh = topology._GLOBAL_MESH
    if (_CONSTRAINTS_DISABLED or mesh is None
            or mesh.shape.get("fsdp", 1) <= 1):
        return grads
    rules = TP_RULES + EP_RULES + PP_RULES + FSDP_RULES

    def scatter_one(dp, axes, path):
        spec = spec_from_logical(axes[1:], rules)  # drop "layers"
        if _MANUAL_AXES:
            spec = _strip_axes_spec(spec, _MANUAL_AXES)
        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, spec)), dp)

    return _walk_with_logical(grads, logical_tree, scatter_one)


def constrain_activation(x, logical_axes: Sequence[Optional[str]]):
    """Apply the activation sharding rules to an intermediate value.

    Usable inside jit-compiled model code; a no-op when no global mesh is
    set (e.g. plain single-device unit tests). This is how models declare
    batch/sequence sharding (dp/fsdp/ep × sp) without knowing the topology.
    """
    from deepspeed_tpu.parallel import topology

    if _CONSTRAINTS_DISABLED:
        return x
    mesh = topology._GLOBAL_MESH
    if mesh is None or all(s == 1 for s in mesh.shape.values()):
        return x
    spec = spec_from_logical(logical_axes, ACT_RULES + TP_RULES)
    if _MANUAL_AXES or _VMAPPED_AXES:
        spec = _strip_axes_spec(spec, _MANUAL_AXES | _VMAPPED_AXES)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
