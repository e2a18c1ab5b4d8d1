"""1-bit compressed-communication optimizers (1-bit Adam family).

Reference: deepspeed/runtime/fp16/onebit/{adam.py:14 OnebitAdam,
zoadam.py:14 ZeroOneAdam, lamb.py:16 OnebitLamb} with the compressed
allreduce backends in runtime/comm/{nccl,compressed}.py
(NcclBackend.compressed_allreduce: sign-compress with per-tensor scale +
per-worker error feedback, allreduce the 1-bit representation).

Algorithm (1-bit Adam, Tang et al.): a full-precision *warmup* phase runs
plain Adam; at ``freeze_step`` the variance term freezes and from then on
only the momentum is communicated, sign-compressed with error feedback —
a 32x reduction in gradient-sync volume.

TPU-native expression: the engine's normal path lets GSPMD insert the
gradient reduction, which leaves nothing to compress. Here the
forward/backward runs inside a ``jax.shard_map`` that is MANUAL over the
dp axis only (``axis_names={'dp'}``; tp/sp stay under GSPMD), so the
per-rank local gradients are visible, and the compressed allreduce is an
explicit ``lax.pmean`` of ``sign(x) * scale`` — riding ICI, with the
error-feedback buffer carried as a per-rank state (leading dp axis).

Constraints (same as the reference's): ZeRO stage <= 1, no optimizer
offload; masters/moments are replicated over dp.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.logging import log_dist

ONEBIT_OPTIMIZERS = ("onebitadam", "zerooneadam", "onebitlamb")


class OneBitState(NamedTuple):
    master: Any   # fp32 master params (replicated over dp)
    m: Any        # momentum (replicated)
    v: Any        # variance (frozen after freeze_step)
    error: Any    # per-rank error feedback, leaves [dp, *shape]
    step: jax.Array


def _tree_zeros_like(tree):
    return jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tree)


def parse_onebit_params(name: str, params: Dict) -> Dict:
    p = dict(params or {})
    out = {
        "kind": name,
        "lr": p.pop("lr", 1e-3),
        "betas": tuple(p.pop("betas", (0.9, 0.999))),
        "eps": p.pop("eps", 1e-8),
        "weight_decay": p.pop("weight_decay", 0.0),
        "freeze_step": p.pop("freeze_step", 100),
        # zerooneadam: variance refresh interval during compression
        # (reference var_update_scaler zoadam.py; deviation documented in
        # build_onebit_step)
        "var_update_interval": p.pop("var_update_interval", 16),
        # onebitlamb: trust-ratio clamp (reference lamb.py coeff bounds)
        "max_coeff": p.pop("max_coeff", 10.0),
        "min_coeff": p.pop("min_coeff", 0.01),
    }
    p.pop("cuda_aware", None)
    p.pop("comm_backend_name", None)
    return out


def build_onebit_step(model, mesh, cfg, opt: Dict, param_shardings,
                      lr_schedule: Optional[Callable]):
    """Returns (init_fn(rng) -> (params, OneBitState),
    step_fn(params, state, batches) -> (params, state, metrics))."""
    gas = cfg.gradient_accumulation_steps
    cdt = cfg.compute_dtype
    beta1, beta2 = opt["betas"]
    eps = opt["eps"]
    wd = opt["weight_decay"]
    freeze_step = opt["freeze_step"]
    kind = opt["kind"]
    base_lr = opt["lr"]
    grad_clip = cfg.gradient_clipping

    dp = mesh.shape.get("dp", 1)

    def init_fn(rng):
        p32 = model.init(rng)
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p32)
        params = jax.tree.map(lambda x: x.astype(cdt), p32)
        m = _tree_zeros_like(p32)
        v = _tree_zeros_like(p32)
        error = jax.tree.map(
            lambda x: jnp.zeros((dp,) + x.shape, jnp.float32), p32)
        return params, OneBitState(p32, m, v, error,
                                   jnp.asarray(0, jnp.int32))

    def local_grads(params, batches, m, error, step):
        """MANUAL over dp: local grads -> compressed/full momentum sync.
        batches leaves: [gas, B/dp, ...]; error leaves [1, *shape]."""
        from deepspeed_tpu.runtime import sharding as shard_lib

        # trace-time: the model's sharding constraints reference mesh axes
        # that are manual inside this shard_map region
        with shard_lib.disable_constraints():
            return _local_grads_inner(params, batches, m, error, step)

    def _local_grads_inner(params, batches, m, error, step):
        def total_loss(p):
            def body(carry, mb):
                loss, _aux = model.loss(p, mb)
                return carry + loss / gas, loss

            total, losses = lax.scan(body, jnp.asarray(0.0, jnp.float32),
                                     batches)
            return total, losses

        (_, losses), grads = jax.value_and_grad(
            total_loss, has_aux=True)(params)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        # candidate momentum from LOCAL grads
        m_cand = jax.tree.map(lambda mm, g: beta1 * mm + (1 - beta1) * g,
                              m, grads)

        def warmup(_):
            g_avg = jax.tree.map(lambda g: lax.pmean(g, "dp"), grads)
            m_new = jax.tree.map(lambda mm, g: beta1 * mm + (1 - beta1) * g,
                                 m, g_avg)
            return m_new, error, g_avg

        def compressed(_):
            def comp_leaf(mc, e):
                c_in = mc + e[0]
                scale = jnp.mean(jnp.abs(c_in))
                comp = jnp.sign(c_in) * scale
                m_new = lax.pmean(comp, "dp")
                new_e = (c_in - comp)[None]
                return m_new, new_e

            treedef = jax.tree.structure(m_cand)
            m_list, e_list = [], []
            for mc, e in zip(jax.tree.leaves(m_cand), jax.tree.leaves(error)):
                mn, ne = comp_leaf(mc, e)
                m_list.append(mn)
                e_list.append(ne)
            m_new = jax.tree.unflatten(treedef, m_list)
            new_e = jax.tree.unflatten(treedef, e_list)
            g_zero = _tree_zeros_like(m_cand)
            return m_new, new_e, g_zero

        m_new, new_error, g_avg = lax.cond(step < freeze_step, warmup,
                                           compressed, operand=None)
        loss_avg = lax.pmean(jnp.mean(losses), "dp")
        return m_new, new_error, g_avg, loss_avg

    batch_spec = P(None, "dp")
    rep = P()

    def step_fn(params, state: OneBitState, batches, lr_override=None):
        """lr_override: fp32 scalar operand; NaN = use the traced
        schedule (the engine's set_lr without a rebuild — same runtime-lr
        technique as the ZeRO++ step, runtime/zeropp.py)."""
        step = state.step
        err_specs = jax.tree.map(lambda _: P("dp"), state.error)
        batch_specs = jax.tree.map(lambda _: batch_spec, batches)

        sm = jax.shard_map(
            partial(local_grads),
            mesh=mesh, axis_names={"dp"},
            in_specs=(rep, batch_specs, rep, err_specs, rep),
            out_specs=(rep, err_specs, rep, rep),
            check_vma=False)
        m_new, new_error, g_avg, loss = sm(params, batches, state.m,
                                           state.error, step)

        in_warmup = step < freeze_step
        # variance: updated in warmup, frozen after (zerooneadam: also
        # refreshed every var_update_interval steps from |m| as a proxy —
        # documented deviation from the reference's local-step schedule,
        # comm volume matches 1-bit Adam)
        def v_warm(v, g):
            return beta2 * v + (1 - beta2) * g * g

        if kind == "zerooneadam":
            refresh = (step % opt["var_update_interval"] == 0)
            v_new = jax.tree.map(
                lambda v, g, mm: jnp.where(
                    in_warmup, v_warm(v, g),
                    jnp.where(refresh, beta2 * v + (1 - beta2) * mm * mm, v)),
                state.v, g_avg, m_new)
        else:
            v_new = jax.tree.map(
                lambda v, g: jnp.where(in_warmup, v_warm(v, g), v),
                state.v, g_avg)

        lr = (lr_schedule(step) if lr_schedule is not None
              else jnp.asarray(base_lr, jnp.float32))
        if lr_override is not None:
            lr = jnp.where(jnp.isnan(lr_override), lr, lr_override)

        bc1 = 1 - beta1 ** (step.astype(jnp.float32) + 1)
        bc2 = 1 - beta2 ** (step.astype(jnp.float32) + 1)

        def upd_leaf(master, mm, vv):
            update = (mm / bc1) / (jnp.sqrt(vv / bc2) + eps)
            if wd:
                update = update + wd * master
            return update

        updates = jax.tree.map(upd_leaf, state.master, m_new, v_new)

        gnorm = jnp.sqrt(sum(jnp.sum(u.astype(jnp.float32) ** 2)
                             for u in jax.tree.leaves(updates)))
        coef = jnp.asarray(1.0, jnp.float32)
        if grad_clip:
            coef = jnp.minimum(1.0, grad_clip / (gnorm + 1e-6))

        if kind == "onebitlamb":
            # layerwise trust ratio (reference lamb.py fused coefficients)
            def lamb_scale(master, u):
                wn = jnp.sqrt(jnp.sum(master.astype(jnp.float32) ** 2))
                un = jnp.sqrt(jnp.sum(u.astype(jnp.float32) ** 2))
                ratio = jnp.where(un > 0, wn / (un + 1e-12), 1.0)
                return jnp.clip(ratio, opt["min_coeff"], opt["max_coeff"])

            master_new = jax.tree.map(
                lambda master, u: master - lr * coef * lamb_scale(master, u) * u,
                state.master, updates)
        else:
            master_new = jax.tree.map(
                lambda master, u: master - lr * coef * u,
                state.master, updates)

        params_new = jax.tree.map(lambda mm: mm.astype(cdt), master_new)
        metrics = {"loss": loss, "lr": lr, "grad_norm": gnorm,
                   "loss_scale": jnp.asarray(1.0),
                   "overflow": jnp.asarray(False),
                   "compressed": ~in_warmup}
        return params_new, OneBitState(master_new, m_new, v_new, new_error,
                                       step + 1), metrics

    return init_fn, step_fn


def validate_onebit_config(cfg) -> None:
    if cfg.zero_optimization.stage > 1:
        raise ValueError(
            f"1-bit optimizers require ZeRO stage <= 1 (reference "
            f"onebit/adam.py constraint), got stage="
            f"{cfg.zero_optimization.stage}")
    off = cfg.zero_optimization.offload_optimizer
    if off is not None and (off.device or "none") != "none":
        raise ValueError("1-bit optimizers are incompatible with "
                         "optimizer offload")
