"""Mixture-of-Experts: top-k gating + expert-parallel dispatch.

Reference: deepspeed/moe/sharded_moe.py — ``top1gating`` :184,
``top2gating`` :291, ``topkgating`` :375, ``MOELayer.forward`` :589-685
(einsum dispatch, two all-to-alls around local experts), aux
load-balancing losses; expert groups deepspeed/utils/groups.py:304.

TPU-native shape: the dispatch/combine tensors are einsums (exactly the
GShard formulation the reference follows), and the "two all-to-alls" are
not explicit calls — expert weights shard over the ``ep`` mesh axis and
the dispatched activations get a sharding constraint onto ``ep``, so
GSPMD emits the token all-to-all pair on ICI. Capacity-style static
shapes keep everything jit-compatible (no ragged dispatch in the train
path; ragged decode lives in the inference stack).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.ops.attention import kernel_gmm_tiles
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.runtime.sharding import constrain_activation


@dataclasses.dataclass(frozen=True)
class GateConfig:
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    drop_tokens: bool = True
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.0
    # the router's rule (:func:`route`): "softmax" over all the outputs, the
    # top_k renormalised; "sigmoid": each output's sigmoid is its score, a
    # per-expert bias joins the *choice* only, the chosen scores are
    # renormalised and multiplied by ``routed_scale``
    scoring: str = "softmax"
    routed_scale: float = 1.0

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r} (softmax | sigmoid)")


def compute_capacity(tokens_per_group: int, cfg: GateConfig,
                     train: bool = True) -> int:
    """Reference _capacity (sharded_moe.py:91)."""
    factor = cfg.capacity_factor if train else cfg.eval_capacity_factor
    cap = int(tokens_per_group * factor * cfg.top_k / cfg.num_experts)
    cap = max(cap, cfg.min_capacity)
    if not cfg.drop_tokens:
        cap = tokens_per_group  # worst case: everyone to one expert
    return min(cap, tokens_per_group * cfg.top_k)


def top_k_gating(logits: jax.Array, cfg: GateConfig, capacity: int
                 ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Generalized top-k gate (covers the reference's top1/top2/topk).

    logits: [G, S, E] (G = groups = batch dim). Returns
    (combine_weights [G,S,E,C], dispatch_mask [G,S,E,C] bool, aux dict).
    """
    G, S, E = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [G,S,E]

    # per-k expert choice with positional priority (earlier tokens win
    # capacity slots, k=0 choices win over k=1 — reference topkgating's
    # sequential locations, sharded_moe.py:375)
    combine = jnp.zeros((G, S, E, capacity), jnp.float32)
    counts = jnp.zeros((G, E), jnp.int32)  # slots used per expert
    remaining = gates
    denom = jnp.zeros((G, S), jnp.float32)
    picks = []
    for _ in range(cfg.top_k):
        idx = jnp.argmax(remaining, axis=-1)  # [G,S]
        picks.append(idx)
        gate_val = jnp.take_along_axis(gates, idx[..., None], axis=-1)[..., 0]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [G,S,E]
        # position of each token within its chosen expert's slots: tokens
        # before me this round + slots used by earlier rounds
        pos_in_exp = jnp.cumsum(onehot, axis=1) - onehot  # [G,S,E]
        pos = (jnp.take_along_axis(pos_in_exp, idx[..., None], axis=-1)[..., 0]
               + jnp.take_along_axis(counts, idx, axis=1).astype(jnp.float32))
        keep = pos < capacity
        gate_kept = jnp.where(keep, gate_val, 0.0)
        denom = denom + gate_kept
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=jnp.float32)  # [G,S,C]
        combine = combine + (gate_kept[..., None, None]
                             * onehot[..., :, None] * pos_oh[..., None, :])
        counts = counts + jnp.sum(
            onehot * keep[..., None].astype(jnp.float32), axis=1).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)  # mask picked expert

    # normalize combine weights over the kept top-k gates (reference
    # normalizes top-k probs, sharded_moe.py topkgating)
    combine = combine / jnp.maximum(denom[..., None, None], 1e-9)
    dispatch = combine > 0.0

    # load-balancing aux loss: E * mean_e(frac_tokens_e * mean_gate_e)
    # (reference l_aux, sharded_moe.py:262)
    me = jnp.mean(gates, axis=(0, 1))  # [E]
    top1_onehot = jax.nn.one_hot(picks[0], E, dtype=jnp.float32)
    ce = jnp.mean(top1_onehot, axis=(0, 1))  # [E]
    l_aux = jnp.sum(me * ce) * E

    aux: Dict[str, jax.Array] = {"l_aux": l_aux}
    if cfg.z_loss_weight:
        zl = jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2)
        aux["l_zloss"] = zl
    # expert counts for observability (reference exp_counts)
    aux["expert_load"] = counts.astype(jnp.float32).mean(axis=0) / max(S, 1)
    return combine, dispatch, aux


def _grouped_unsupported_reason(cfg: GateConfig) -> Optional[str]:
    """Why the grouped path can't run on the current mesh (None = it can).

    The grouped engine composes with dp/fsdp (token-parallel shards), ep
    (experts partitioned per shard, tokens routed by two all-to-alls),
    sp (another token axis), tp (FFN dim split + deferred psum) and pp
    (the dispatch shard_map nests inside the pipeline's manual-pp stage
    body over the remaining auto axes). The one exclusion left: expert
    counts that don't divide over ep."""
    from deepspeed_tpu.parallel import topology as topo

    from deepspeed_tpu.runtime import sharding as _sharding

    mesh = topo._GLOBAL_MESH
    if mesh is None:
        return None
    ep = mesh.shape.get("ep", 1)
    if ep > 1 and cfg.num_experts % ep:
        return f"num_experts={cfg.num_experts} not divisible by ep={ep}"
    # the dispatch shard_map must manualize ep/tp itself (its collectives
    # and specs reference them); an enclosing region that already
    # manualized them (none in-tree does) can't host the grouped path
    pre_manual = sorted(a for a in ("ep", "tp")
                        if a in _sharding._MANUAL_AXES
                        and mesh.shape.get(a, 1) > 1)
    if pre_manual:
        return f"axes {pre_manual} already manual in the enclosing region"
    # under qgZ's per-group gradient vmap the token axes are mapped, not
    # mesh-sharded — a shard_map can't map a vmapped dim, so the einsum
    # dispatch (plain GSPMD ops, vmappable) carries MoE there. This is an
    # engine-internal trace mode, not a user mesh limit: soft (see
    # moe_ffn — even an explicit impl="grouped" degrades here instead of
    # raising, since the same config trains fine outside the qgZ vmap)
    vmapped = sorted(a for a in ("dp", "fsdp", "ep", "sp")
                     if a in getattr(_sharding, "_VMAPPED_AXES", frozenset())
                     and mesh.shape.get(a, 1) > 1)
    if vmapped:
        return (f"token axes {vmapped} are vmapped (qgZ per-group grads): "
                "grouped dispatch uses the einsum path [soft]")
    return None


def moe_ffn(x: jax.Array, router_w: jax.Array, expert_params: Dict[str, jax.Array],
            cfg: GateConfig, activation: str = "swiglu", train: bool = True,
            impl: str = "auto") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full MoE FFN block (reference MOELayer.forward sharded_moe.py:589).

    x: [B, S, H]; router_w: [H, E]; expert_params: wi/wo(/wg) with leading
    expert dim [E, ...] sharded over the ep mesh axis.

    impl: "einsum" = capacity-padded GShard dispatch (drops overflow
    tokens, pads underflow — fixed E*C flops); "grouped" = dropless
    grouped-GEMM execution (reference GroupedExperts, ep_experts.py:136 —
    exact top-k flops regardless of imbalance), expert-parallel over ep
    with two all-to-alls and tp-split FFNs (see moe_ffn_dropless).
    "auto"/"grouped" take the grouped path on every mesh (under pp the
    dispatch nests inside the pipeline stage body) except E % ep != 0 —
    "auto" falls back to einsum there with a telemetry count
    ("moe.grouped_fallback") and a one-time warning; an explicit
    "grouped" raises instead (a silent numeric change is worse than an
    error).
    """
    if impl in ("auto", "grouped"):
        reason = _grouped_unsupported_reason(cfg)
        if reason is None:
            return moe_ffn_dropless(x, router_w, expert_params, cfg,
                                    activation=activation, train=train)
        if impl == "grouped" and "[soft]" not in reason:
            # an explicit request must not silently change numerics (the
            # einsum path drops tokens differently); only "auto" degrades.
            # Exception: [soft] reasons are engine-internal trace modes
            # (the qgZ per-group vmap) — raising would make a valid user
            # config crash only when qgZ arms, so those degrade with
            # telemetry for explicit "grouped" too.
            raise ValueError(
                f"moe_ffn: impl='grouped' is unsupported on this mesh: "
                f"{reason} (use impl='auto' to allow the einsum fallback)")
        from deepspeed_tpu.utils import telemetry
        telemetry.count("moe.grouped_fallback", reason)
    B, S, H = x.shape
    dt = x.dtype
    logits = jnp.einsum("bsh,he->bse", x, router_w.astype(dt))
    capacity = compute_capacity(S, cfg, train=train)
    combine, dispatch, aux = top_k_gating(logits, cfg, capacity)

    # dispatch: [B,S,H] x [B,S,E,C] -> [B,E,C,H]; constraining the E dim
    # onto ep makes GSPMD emit all-to-all #1 (reference _AllToAll
    # sharded_moe.py:97)
    dispatched = jnp.einsum("bsh,bsec->bech", x, dispatch.astype(dt))
    dispatched = constrain_activation(dispatched, ("batch", "expert", None, "embed"))

    wi, wo = expert_params["wi"].astype(dt), expert_params["wo"].astype(dt)
    if activation == "swiglu":
        wg = expert_params["wg"].astype(dt)
        gate = jnp.einsum("bech,ehf->becf", dispatched, wg)
        up = jnp.einsum("bech,ehf->becf", dispatched, wi)
        hidden = jax.nn.silu(gate) * up
    else:
        hidden = jax.nn.gelu(jnp.einsum("bech,ehf->becf", dispatched, wi))
    hidden = constrain_activation(hidden, ("batch", "expert", None, "mlp"))
    expert_out = jnp.einsum("becf,efh->bech", hidden, wo)

    # combine: all-to-all #2 back to token layout
    out = jnp.einsum("bech,bsec->bsh", expert_out,
                     combine.astype(dt))
    out = constrain_activation(out, ("batch", "seq", "embed"))
    return out, aux


@dataclasses.dataclass(frozen=True)
class Glu:
    """A gated feed-forward's activation, ``hidden = glu(x W_g, x W_u)``:
    ``kind`` "swiglu" is ``silu(gate) * up``; "swigluoai" (the gpt-oss form)
    clamps both first, the gate from above and the up-projection on both
    sides at ``limit``, and is ``(up + 1) * gate * sigmoid(alpha * gate)``."""

    kind: str = "swiglu"
    alpha: float = 1.702
    limit: float = 7.0

    def __post_init__(self):
        if self.kind not in ("swiglu", "swigluoai"):
            raise ValueError(f"glu kind {self.kind!r} (swiglu | swigluoai)")

    def __call__(self, gate: jax.Array, up: jax.Array) -> jax.Array:
        if self.kind == "swiglu":
            return jax.nn.silu(gate) * up
        gate = jnp.minimum(gate, self.limit)
        up = jnp.clip(up, -self.limit, self.limit)
        return (up + 1.0) * gate * jax.nn.sigmoid(self.alpha * gate)


SWIGLU = Glu()


def _expert_ffn(sorted_x: jax.Array, group_sizes: jax.Array,
                expert_params: Dict[str, jax.Array], activation,
                dt, layer=None, tile_limits=None, metadata=None
                ) -> jax.Array:
    """Grouped-GEMM expert FFN over rows sorted by (local) expert. With
    ``layer`` the expert leaves are stacks ``[L, E, ...]`` read at that
    (traced) layer inside the kernel, and the three products share the
    work list ``metadata`` (their row tile is one: it follows from the
    rows and the experts alone). The kernel chooses each product's tiles
    from its shapes; ``tile_limits`` (``block_m`` / ``block_n`` /
    ``block_k``) are upper bounds on that choice. ``activation``: "swiglu",
    a :class:`Glu`, or anything else for the ungated GELU."""
    def gmm(lhs, rhs, sizes):
        tiles = gm.choose_tiles(lhs.shape[0], *rhs.shape[-2:], rhs.shape[-3],
                                lhs.dtype, **(tile_limits or {}))
        if layer is None:
            return gm.gmm(lhs, rhs, sizes, *tiles)
        return gm.gmm_layer(lhs, rhs, sizes, layer, *tiles, metadata=metadata)
    wi, wo = expert_params["wi"].astype(dt), expert_params["wo"].astype(dt)
    if activation == "swiglu":
        activation = SWIGLU
    if isinstance(activation, Glu):
        wg = expert_params["wg"].astype(dt)
        hidden = activation(gmm(sorted_x, wg, group_sizes),
                            gmm(sorted_x, wi, group_sizes))
    else:
        hidden = jax.nn.gelu(gmm(sorted_x, wi, group_sizes))
    return gmm(hidden, wo, group_sizes)                     # [M, H-or-H_tp]


def route_top_k(y: jax.Array, router_w: jax.Array, top_k: int):
    """Softmax routing in float32 over *all* the router's outputs: the
    ``top_k`` largest probabilities, renormalised to sum to one. y [T, H],
    router_w [H, E]. Returns (weights [T, k] float32, experts [T, k])."""
    logits = jnp.einsum("th,he->te", y.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    top, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return top / jnp.sum(top, axis=-1, keepdims=True), idx.astype(jnp.int32)


def route(y: jax.Array, router_w: jax.Array, cfg: GateConfig,
          bias: Optional[jax.Array] = None, keep=lambda idx: idx):
    """The router's rule as ``cfg`` states it, in float32 at ``HIGHEST``:
    :func:`route_top_k`, or with ``scoring`` "sigmoid" the scores ``sigmoid(y
    W_r)``, the ``top_k`` of ``score + bias`` chosen (``bias [E]``: the
    correction that balances load takes part in the choice and never in a
    weight), the chosen *scores* renormalised to sum to one and multiplied by
    ``routed_scale``. Returns (weights [T, k] float32, experts [T, k]). The
    sigmoid rule reads its weights at ``keep(experts)``: where ``keep`` names
    the choice for a checkpoint, the backward pass does not choose again."""
    if cfg.scoring == "softmax":
        return route_top_k(y, router_w, cfg.top_k)
    score = jax.nn.sigmoid(jnp.einsum(
        "th,he->te", y.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    choose = score if bias is None else score + bias.astype(jnp.float32)
    idx = keep(lax.top_k(choose, cfg.top_k)[1].astype(jnp.int32))
    top = _chosen(score, idx)
    w = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scale, idx


def bias_update(load: jax.Array, rate: float) -> jax.Array:
    """What a step adds to the bias of :func:`route`'s choice, from the
    tokens each output got in it (``load [..., E]``): ``rate`` up for an
    output under the mean, down for one over it, the mean of the change
    taken off (the bias balances; it does not drift). No gradient reaches the
    bias: a trainer applies this between steps
    (``runtime/engine.py``, a model's ``param_deltas``)."""
    load = load.astype(jnp.float32)
    d = rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
    return d - jnp.mean(d, axis=-1, keepdims=True)


@jax.named_scope("moe")
def moe_ffn_share(y: jax.Array, router_w: jax.Array,
                  expert_params: Dict[str, jax.Array], cfg: GateConfig, *,
                  offset: int = 0, shared: Optional[Dict] = None,
                  valid: Optional[jax.Array] = None, layer=None,
                  router_bias: Optional[jax.Array] = None,
                  capacity: Optional[int] = None, glu: Glu = SWIGLU
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """An expert layer that holds a *share* of the experts: one chip's part
    of a layer whose ``cfg.num_experts`` experts are divided over the chips
    of an expert-parallel deployment (model-configs guide, section 4).

    The router runs over all ``cfg.num_experts`` outputs by ``cfg``'s rule
    (:func:`route`; ``router_bias`` is the sigmoid rule's per-expert
    correction); the experts held here are ``offset .. offset + E_held``
    (``expert_params`` leaves ``[E_held, ...]``, or ``[L, E_held, ...]`` with
    ``layer``). The result is what *these* experts add for the tokens routed
    to them, plus — given ``shared`` (``wg``, ``wi``, ``wo`` and, for a model
    that has one, the gate vector ``gate``) — the shared expert, behind its
    sigmoid gate or added as it is, which every chip computes alike. ``glu``
    is the activation of the routed and the shared experts alike. A token
    none of whose experts live here gets the shared expert alone. Nothing
    stands in for the absent chips: on one chip there is no exchange; on a
    mesh with an ``ep`` axis the same layer is :func:`moe_ffn_dropless`,
    whose shards each hold ``E / ep`` and exchange rows.

    y [T, H] (already normed); ``valid [T]`` marks real tokens (padding is
    routed nowhere). Returns (out [T, H] in y's type, counts): ``pairs``, the
    (token, expert) pairs routed here, ``experts_hit``, the held experts that
    got a row, and — int32 scalars all — with ``layer`` (the serving
    programs' counters) ``work_items``, the (row tile, expert) pairs each of
    the three grouped products multiplies, without it (the training step's)
    ``max_rows``, the fullest held expert's rows, ``dropped``, and ``load
    [num_experts]``, the tokens that chose each of the router's outputs.

    Rows sort by local expert; pairs routed elsewhere sort last and lie beyond
    the groups' sum, where the grouped product yields zeros (and its backward
    zero row gradients, and nothing of them in the experts'). Without ``layer``
    the experts are one layer's leaves and the products differentiate
    (``gm.gmm``): the training path. Most of the ``T * top_k`` rows are routed
    elsewhere (seven eighths at a share of an eighth), so it may bound the
    buffer: ``capacity`` (a multiple of 128) keeps the first rows of the sorted
    order, the local ones; local pairs beyond it are ``dropped`` (counted,
    adding nothing): a static row budget as :func:`_ep_capacity` is for the
    exchange. ``router_bias`` takes part in the choice alone and gets no
    gradient. That path names its routing's integers (:func:`_keep_routing`): a
    checkpoint told of ``ROUTING_NAME`` keeps them, and sorts once."""
    T, H = y.shape
    held = expert_params["wi"].shape[-3]
    k = cfg.top_k
    if router_bias is not None:
        router_bias = lax.stop_gradient(router_bias)
    with jax.named_scope("moe_route"):
        keep = _keep_routing if layer is None else (lambda ints: ints)
        w, idx = route(y, router_w, cfg, router_bias, keep)
        here = (idx >= offset) & (idx < offset + held)
        if valid is not None:
            here = here & valid[:, None]
        m0 = T * k
        m = ((m0 + 127) // 128) * 128
        local = jnp.where(here, idx - offset, held).reshape(-1)
        flat_w = jnp.where(here, w, 0.0).reshape(-1)
        if m > m0:
            local = jnp.concatenate(
                [local, jnp.full((m - m0,), held, local.dtype)])
            flat_w = jnp.concatenate([flat_w, jnp.zeros((m - m0,), w.dtype)])
        order = jnp.argsort(local, stable=True)
        # (a compare and a sum, and below a division: a bincount is a
        # scatter-add and ``token[order]`` a gather of scalars, :func:`_chosen`)
        group_sizes = jnp.sum(
            local[:, None] == jnp.arange(held, dtype=local.dtype),
            axis=0, dtype=jnp.int32)
        dropped = jnp.int32(0)
        if capacity is not None and capacity < m:
            if capacity % 128 or layer is not None:
                raise ValueError(f"capacity={capacity}: a multiple of 128, "
                                 f"on the path without a layer index")
            m, order = capacity, order[:capacity]
            dropped = jnp.maximum(jnp.sum(group_sizes) - m, 0)
        order, group_sizes = map(keep, (order, group_sizes))
        # pair p is token p // k's, the padded tail token 0's
        row_token = jnp.where(order < m0, order // k, 0)
        # one work list for the three products, which share the row tile (the
        # largest that divides m); the differentiable product makes its own
        work = None if layer is None else gm.make_group_metadata(
            group_sizes, m, gm.choose_tiles(m, H, H, held, y.dtype)[0])
    with jax.named_scope("moe_experts"):
        out = _expert_ffn(y[row_token], group_sizes, expert_params, glu,
                          y.dtype, layer=layer, metadata=work)
        contrib = out.astype(jnp.float32) * flat_w[order][:, None]
        total = jnp.zeros((T, H), jnp.float32).at[row_token].add(contrib)
    if shared is not None:
        with jax.named_scope("moe_shared"):
            dt = y.dtype
            hid = glu(y @ shared["wg"].astype(dt), y @ shared["wi"].astype(dt))
            out = (hid @ shared["wo"].astype(dt)).astype(jnp.float32)
            if "gate" in shared:
                out = out * jax.nn.sigmoid(jnp.einsum(
                    "th,h->t", y.astype(jnp.float32),
                    shared["gate"].astype(jnp.float32)))[:, None]
            total = total + out
    counts = {"pairs": jnp.sum(here).astype(jnp.int32),
              "experts_hit": jnp.sum(group_sizes > 0).astype(jnp.int32)}
    if layer is None:       # the training path's counters
        counts["max_rows"] = jnp.max(group_sizes).astype(jnp.int32)
        counts["dropped"] = dropped.astype(jnp.int32)
        # every output's tokens, held here or not: what a balancing bias
        # is updated from
        chosen = idx if valid is None else jnp.where(
            valid[:, None], idx, cfg.num_experts)
        # (a compare and a sum: a bincount of T * top_k keys is a
        # scatter-add, 1.1 ms a layer's forward at 131,072 keys on the chip)
        counts["load"] = jnp.sum(
            chosen[..., None] == jnp.arange(cfg.num_experts,
                                            dtype=chosen.dtype),
            axis=(0, 1), dtype=jnp.int32)
    else:                   # the serving programs'
        counts["work_items"] = gm.work_items(work)
    return total.astype(y.dtype), counts


def _ep_capacity(m0: int, ep: int, cfg: GateConfig, train: bool) -> int:
    """Static per-(src,dst) row budget for the expert all-to-all.

    drop_tokens=False → the true worst case (every local row to one
    owner shard): genuinely dropless, at ep× the balanced buffer. With
    drop_tokens, capacity pools at *shard* level (an owner's hot expert
    borrows headroom from its cold co-residents — strictly fewer drops
    than the reference's per-expert capacity at the same factor,
    sharded_moe.py:91)."""
    if cfg.drop_tokens:
        factor = cfg.capacity_factor if train else cfg.eval_capacity_factor
        cap = int(-(-factor * m0 // ep))                    # ceil
        cap = max(cap, cfg.min_capacity)
        cap = min(cap, m0)
    else:
        cap = m0
    return ((cap + 127) // 128) * 128                       # MXU row tile


def _dropless_shard_core(x: jax.Array, router_w: jax.Array,
                         expert_params: Dict[str, jax.Array],
                         cfg: GateConfig, activation: str, *,
                         ep_axis: Optional[str] = None, ep: int = 1,
                         tp_axis: Optional[str] = None, tp: int = 1,
                         train: bool = True
                         ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Per-shard dropless dispatch (runs inside shard_map, or bare when
    there is no mesh).

    ep == 1: rows sort by expert locally and run through the whole
    (locally resident) expert stack — the original single-shard engine.

    ep > 1: *expert parallelism*. ``expert_params`` hold only this
    shard's E/ep experts; each row's owner shard is ``expert // e_loc``
    and rows travel by two all-to-alls over ``ep_axis`` (the reference's
    dispatch/combine pair, sharded_moe.py:589-685) with a static
    per-(src,dst) row budget (:func:`_ep_capacity`). Overflow rows are
    dropped at the sender with zero combine weight and counted in
    ``stats['ep_dropped_frac']``.

    tp > 1: ``expert_params`` additionally hold only this shard's F/tp
    slice of every expert; the combine output is a partial sum and is
    psum'd over ``tp_axis`` at the end (deferred past the return
    all-to-all — [tokens,H] is top_k× smaller than the row buffer). A
    routing digest cross-checks that all tp peers dispatched
    identically (reference TP-consistency digests, ep_tp_dispatch.py:99).

    Stats are shaped so an unweighted mean over equal-sized token shards
    reproduces the global statistic exactly.
    """
    B, S, H = x.shape
    E, k = cfg.num_experts, cfg.top_k
    e_loc = E // ep
    # the expert-parallel guarantee, enforced at trace time: a shard only
    # ever holds E/ep experts (no whole-stack gather can have happened)
    assert expert_params["wi"].shape[0] == e_loc, (
        f"expected {e_loc} experts per ep shard, got "
        f"{expert_params['wi'].shape[0]}")
    dt = x.dtype
    logits = jnp.einsum("bsh,he->bse", x, router_w.astype(dt))
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_vals, top_idx = lax.top_k(gates, k)
    weights = top_vals / jnp.maximum(
        jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)

    tokens = B * S
    m0 = tokens * k
    flat_x = x.reshape(tokens, H)
    flat_expert = top_idx.reshape(-1).astype(jnp.int32)     # [m0]
    flat_w = weights.reshape(-1)                            # fp32
    token_idx = jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), k)

    stats = {
        "me": jnp.mean(gates, axis=(0, 1)),                          # [E]
        "ce": jnp.mean(jax.nn.one_hot(top_idx[..., 0], E,
                                      dtype=jnp.float32), axis=(0, 1)),
        "zsq": jnp.mean(jax.nn.logsumexp(
            logits.astype(jnp.float32), axis=-1) ** 2)[None],
        "expert_load": (jnp.bincount(flat_expert, length=E)
                        .astype(jnp.float32) / max(tokens, 1)),
        "ep_dropped_frac": jnp.zeros((1,), jnp.float32),
        "dispatch_digest_mismatch": jnp.zeros((1,), jnp.float32),
    }
    if tp > 1:
        # dispatch digest: order-sensitive checksum of the routing
        # decision; pmax==pmin over tp ⇔ every tp peer will slice the
        # same rows to the same experts (they see replicated x, so any
        # mismatch means nondeterminism that would corrupt the deferred
        # psum row alignment)
        dig = jnp.sum(flat_expert.astype(jnp.uint32)
                      * (jnp.arange(m0, dtype=jnp.uint32)
                         * jnp.uint32(2654435761) + jnp.uint32(12345)))
        mismatch = lax.pmax(dig, tp_axis) != lax.pmin(dig, tp_axis)
        stats["dispatch_digest_mismatch"] = \
            mismatch.astype(jnp.float32)[None]

    if ep > 1:
        dest = flat_expert // e_loc                         # owner shard
        cap = _ep_capacity(m0, ep, cfg, train)
        # position of row j within its (src→dest) budget: rows fill
        # slots in row order
        oh = (dest[:, None] == jnp.arange(ep, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - oh,
                                  dest[:, None], axis=1)[:, 0]
        keep = pos < cap
        pos_c = jnp.minimum(pos, cap - 1)
        kf = keep.astype(dt)
        # renormalize combine weights over *kept* gates per token (the
        # einsum path and reference topkgating normalize over kept top-k
        # probs; without this a token whose row overflowed the budget
        # would lose that weight mass entirely instead of redistributing
        # it to its surviving experts)
        keep_f = keep.astype(jnp.float32)
        kept_mass = jnp.zeros((tokens,), jnp.float32).at[token_idx].add(
            flat_w * keep_f)
        flat_w = flat_w * keep_f / jnp.maximum(kept_mass[token_idx], 1e-9)
        rows_x = flat_x[token_idx]                          # [m0, H]
        # packed send buffers: [ep*cap, H] rows + [ep*cap] local-expert
        # tags (0 = padding slot); kept slots are unique so scatter-add
        # is exact, dropped rows add zeros into the clamped last slot
        send_x = jnp.zeros((ep * cap, H), dt).at[
            dest * cap + pos_c].add(rows_x * kf[:, None])
        tag = (flat_expert % e_loc + 1) * keep
        send_tag = jnp.zeros((ep * cap,), jnp.int32).at[
            dest * cap + pos_c].add(tag)
        # all-to-all #1 (dispatch): block d of mine → shard d; block s
        # of the result ← shard s's rows for my experts
        recv_x = lax.all_to_all(send_x, ep_axis, 0, 0, tiled=True)
        recv_tag = lax.all_to_all(send_tag, ep_axis, 0, 0, tiled=True)

        m_rows = ep * cap
        valid = recv_tag > 0
        local_e = jnp.where(valid, recv_tag - 1, e_loc - 1)
        order = jnp.argsort(local_e, stable=True)
        sorted_x = recv_x[order]
        group_sizes = jnp.bincount(local_e, length=e_loc).astype(jnp.int32)
        expert_out = _expert_ffn(sorted_x, group_sizes, expert_params,
                                 activation, dt,            # [m_rows, H]
                                 tile_limits=kernel_gmm_tiles())
        unsorted = jnp.zeros((m_rows, H), dt).at[order].set(expert_out)
        # all-to-all #2 (combine): results return to their source shard
        back = lax.all_to_all(unsorted, ep_axis, 0, 0, tiled=True)
        out_rows = back[dest * cap + pos_c] * kf[:, None]   # [m0, H]
        contrib = out_rows.astype(jnp.float32) * flat_w[:, None]
        stats["ep_dropped_frac"] = (
            jnp.sum(~keep).astype(jnp.float32) / max(m0, 1))[None]
        row_token = token_idx
    else:
        # local sort path: pad rows to the MXU tile; padding rows carry
        # zero combine weight and land in the last group
        m = ((m0 + 127) // 128) * 128
        pad = m - m0
        if pad:
            flat_expert = jnp.concatenate(
                [flat_expert, jnp.full((pad,), E - 1, flat_expert.dtype)])
            flat_w = jnp.concatenate([flat_w, jnp.zeros((pad,), flat_w.dtype)])
            token_idx = jnp.concatenate(
                [token_idx, jnp.zeros((pad,), token_idx.dtype)])
        order = jnp.argsort(flat_expert, stable=True)       # [M]
        row_token = token_idx[order]
        flat_w = flat_w[order]
        group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)
        sorted_x = flat_x[row_token]                        # [M, H] gather
        expert_out = _expert_ffn(sorted_x, group_sizes, expert_params,
                                 activation, dt,
                                 tile_limits=kernel_gmm_tiles())
        contrib = expert_out.astype(jnp.float32) * flat_w[:, None]

    # combine accumulates in fp32 (bf16 scatter-add would stack rounding
    # per top-k contribution); one cast back at the end
    out = jnp.zeros((tokens, H), jnp.float32).at[row_token].add(contrib)
    if tp > 1:
        out = lax.psum(out, tp_axis)                        # F/tp partials
    out = out.astype(dt).reshape(B, S, H)
    return out, stats


def _aux_from_stats(stats: Dict[str, jax.Array], cfg: GateConfig
                    ) -> Dict[str, jax.Array]:
    """Same aux-loss formulas as top_k_gating, from (globally averaged)
    routing statistics."""
    E = cfg.num_experts
    aux = {"l_aux": jnp.sum(stats["me"] * stats["ce"]) * E,
           "expert_load": stats["expert_load"]}
    if cfg.z_loss_weight:
        aux["l_zloss"] = stats["zsq"][0]
    for key in ("ep_dropped_frac", "dispatch_digest_mismatch"):
        if key in stats:
            aux[key] = stats[key][0]
    return aux


_STAT_KEYS = ("me", "ce", "zsq", "expert_load", "ep_dropped_frac",
              "dispatch_digest_mismatch")


def moe_ffn_dropless(x: jax.Array, router_w: jax.Array,
                     expert_params: Dict[str, jax.Array], cfg: GateConfig,
                     activation: str = "swiglu", train: bool = True
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dropless MoE FFN via grouped GEMMs (reference GroupedExperts,
    moe/ep_experts.py:136, executed through the two-all-to-all structure
    of MOELayer.forward, sharded_moe.py:589-685).

    Tokens sort by chosen expert (stable argsort keeps static shapes),
    experts execute as one grouped matmul per projection
    (ops/pallas/grouped_matmul.py), and outputs scatter-add back weighted
    by the gate — exactly top_k expert-FFNs per token with no capacity
    padding, flops independent of routing imbalance.

    Mesh composition (a Pallas call can't be GSPMD-partitioned, so the
    whole dispatch runs inside one shard_map):

      dp/fsdp/sp  token axes — each shard routes its own tokens.
      ep          experts *partition* over the axis (in_spec P('ep') on
                  the stacked expert dim: a shard only ever sees E/ep
                  experts — no whole-stack gather); tokens travel to
                  their owner shard and back via two all-to-alls.
      tp          every expert's FFN dim splits over tp (in_spec on the
                  mlp dim); the combine is psum'd over tp, and routing
                  digests assert tp peers dispatched identically.
      fsdp        the ZeRO-3 param fetch: the expert in_spec leaves the
                  embed dim unsharded, so GSPMD all-gathers it over fsdp
                  on use (stage-3 semantics, never over ep).
      pp          when called inside the pipeline's manual-pp stage body
                  (runtime/sharding.manual_axes tracks it), the dispatch
                  shard_map nests: it takes the context *abstract* mesh
                  and manualizes only the still-auto axes, so the two
                  all-to-alls and the tp psum run per pipeline stage —
                  the reference's MoE-inside-pipe composition
                  (sharded_moe.py:589 under runtime/pipe/engine.py:60).
    """
    from deepspeed_tpu.parallel import topology as topo
    from deepspeed_tpu.runtime import sharding as _sharding

    mesh = topo._GLOBAL_MESH
    manual = _sharding._MANUAL_AXES
    sizes = dict(mesh.shape) if mesh is not None else {}
    ep, tp = sizes.get("ep", 1), sizes.get("tp", 1)
    B_in, S_in = x.shape[0], x.shape[1]
    # token axes only shard what divides: a serve-time batch of 2 on a
    # dp=2×ep=2 mesh shards over dp and *replicates* over ep — the ep
    # dispatch still partitions experts and routes correctly (each source
    # gets its own copies back), it just computes redundantly across the
    # unused token axis. Axes already manual in an enclosing region (the
    # pipeline's pp, the ZeRO++ dp region) can't be re-manualized or
    # referenced in this shard_map's specs — they drop out of the token
    # axes (the enclosing region already localized them).
    def _auto(a: str) -> int:
        return 1 if a in manual else sizes.get(a, 1)

    batch_axes, prod = [], 1
    for a in ("dp", "fsdp", "ep"):
        sz = _auto(a)
        if sz > 1 and B_in % (prod * sz) == 0:
            batch_axes.append(a)
            prod *= sz
    batch_axes = tuple(batch_axes)
    sp = _auto("sp") if S_in % max(_auto("sp"), 1) == 0 else 1
    # token axes the batch dim can't absorb fall through to the sequence
    # dim: routing is per-token, so a batch of 1 still shards its S
    # tokens over ep/dp/fsdp (the dryrun's B=1,S=32,ep=2 case) instead
    # of replicating the whole dispatch on every ep shard
    seq_axes, sprod = [], max(sp, 1)
    for a in ("dp", "fsdp", "ep"):
        sz = _auto(a)
        if sz > 1 and a not in batch_axes and S_in % (sprod * sz) == 0:
            seq_axes.append(a)
            sprod *= sz
    seq_axes = tuple(seq_axes)
    placed = set(batch_axes) | set(seq_axes)
    if mesh is not None and (
            any(_auto(a) > 1 and a not in placed
                for a in ("dp", "fsdp", "ep"))
            or sp != _auto("sp")):
        from deepspeed_tpu.utils import telemetry
        telemetry.count(
            "moe.grouped_replicated_tokens",
            f"batch {B_in}x{S_in} not shardable over all token axes "
            f"{ {a: sizes.get(a, 1) for a in ('dp', 'fsdp', 'ep', 'sp')} }")
    if mesh is None or (not batch_axes and not seq_axes
                        and tp == 1 and sp == 1 and ep == 1):
        out, stats = _dropless_shard_core(x, router_w, expert_params, cfg,
                                          activation, train=train)
        out = constrain_activation(out, ("batch", "seq", "embed"))
        return out, _aux_from_stats(stats, cfg)

    if ep > 1 and cfg.num_experts % ep:
        raise ValueError(
            f"moe_ffn_dropless: num_experts={cfg.num_experts} must divide "
            f"over ep={ep}")

    from jax.sharding import PartitionSpec as P

    ep_ax = "ep" if ep > 1 else None
    tp_ax = "tp" if tp > 1 else None
    sp_ax = "sp" if sp > 1 else None
    seq_entry = seq_axes + ((sp_ax,) if sp_ax else ())
    token_axes = batch_axes + seq_entry

    def local_fn(x, router_w, experts):
        out, stats = _dropless_shard_core(
            x, router_w, experts, cfg, activation,
            ep_axis=ep_ax, ep=ep, tp_axis=tp_ax, tp=tp, train=train)
        return out, jax.tree.map(lambda s: s[None], stats)  # lead shard dim

    x_spec = P(batch_axes or None, seq_entry or None, None)
    # stacked experts: expert dim stays on ep, mlp dim on tp, embed dim
    # gathered (the ZeRO-3 fetch — over fsdp only)
    exp_specs = {"wi": P(ep_ax, None, tp_ax), "wo": P(ep_ax, tp_ax, None)}
    if "wg" in expert_params:
        exp_specs["wg"] = P(ep_ax, None, tp_ax)
    stat_spec = {k: P(token_axes or None) for k in _STAT_KEYS}
    if manual:
        # nested inside a partial-manual region (the pipeline stage body
        # is manual over pp): shard_map must take the context abstract
        # mesh and may only manualize the axes still under GSPMD
        sm_mesh = jax.sharding.get_abstract_mesh()
    else:
        sm_mesh = mesh
    names = frozenset(a for a in mesh.axis_names if a not in manual)
    out, stats_sh = jax.shard_map(
        local_fn, mesh=sm_mesh,
        in_specs=(x_spec, P(), exp_specs),
        out_specs=(x_spec, stat_spec), axis_names=names, check_vma=False,
    )(x, router_w, expert_params)
    stats = jax.tree.map(lambda s: jnp.mean(s, axis=0), stats_sh)
    out = constrain_activation(out, ("batch", "seq", "embed"))
    return out, _aux_from_stats(stats, cfg)


# The name under which the training path of :func:`moe_ffn_share` tags its
# routing's integers for ``jax.checkpoint``. A model that wraps an expert
# layer hands it to ``checkpoint_wrapper(..., kept_names=(ROUTING_NAME,))``
# (``models/hybrid.py::hidden_states``), and the layer's backward pass gets
# the integers back instead of running ``lax.top_k``, the stable sort and the
# count of the groups a second time (PERF.md section 6, PR 47).
ROUTING_NAME = "moe_routing"


def _keep_routing(ints: jax.Array) -> jax.Array:
    """Tag one of an expert layer's routing results as worth keeping across
    a checkpoint under every policy: the experts chosen ``idx [T, top_k]``
    and, after the cut to ``capacity``, the sorted order ``order [M]`` and
    the held experts' ``group_sizes``; int32 all, 4 bytes a pair and 4 a row
    of the buffer (524 KB + 98 KB + 64 B a layer at 16,384 tokens, top-8 and
    24,576 rows). No gradient flows through an integer, so the kept ones are
    the ones a second run would make; the scores, the weights, every product
    and what is arithmetic on a kept integer (a row's token) stay the
    policy's to decide. An identity where nothing wraps the layer. (Down here
    so that no line above a grouped product's call site moves: ROADMAP.md
    S10.)"""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(ints, ROUTING_NAME)


def _chosen(score: jax.Array, idx: jax.Array) -> jax.Array:
    """``jnp.take_along_axis(score, idx, axis=-1)`` (score [T, E], idx [T, k])
    with no indexed access: a compare, a select and a sum over the outputs,
    which XLA fuses into one pass and never writes out as ``[k, E, T]``. One
    term of each sum is not zero, so the value is the gather's to the bit;
    the transpose is a select and a sum over ``k``, and a token's ``idx`` are
    distinct, so each score gets at most one cotangent: the scatter-add's to
    the bit. On the chip a gather or a scatter of scalars runs at 7-9 ns an
    element (1.0 ms a layer's forward at 16,384 tokens' 8 of 128, again in
    the recomputation, 1.5 ms a step in the transpose); the same rule makes
    :func:`moe_ffn_share`'s group sizes and each sorted row's token. The
    tokens lie along the lanes (``[k, E, T]``): summed over the lanes
    (``[T, k, E]``) the same pass is 0.42 ms and not 0.02. The barrier keeps
    XLA from folding the caller's sum over ``k`` into this one, which would
    add a token's ``k`` scores in another order than the gather's were
    (PERF.md section 6, PR 51)."""
    outputs = jnp.arange(score.shape[-1], dtype=idx.dtype)
    top = jnp.sum(jnp.where(idx.T[:, None, :] == outputs[None, :, None],
                            score.T[None, :, :], 0.0), axis=1)
    return lax.optimization_barrier(top.T)
