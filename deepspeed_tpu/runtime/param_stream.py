"""ZeRO-Infinity parameter streaming — the model-agnostic protocol.

The reference's ``offload_param`` works on any module tree: the param
swapper intercepts each submodule's parameters on use
(deepspeed/runtime/zero/partitioned_param_swapper.py,
partition_parameters.py:1188 fetch on pre-forward). The XLA analog
cannot hook arbitrary Python modules — the compiled program must contain
the host→device copies — so the contract is a *protocol* instead:

  * the engine pins a model's declared stacked-parameter subtrees to
    pinned host memory (``Engine._setup_param_host_offload``), and
  * the model's ``apply`` runs those stacks through
    :func:`scan_streamed` (or fetches slices with :func:`fetch_slice`),
    so one layer's params occupy HBM at a time and the remat replay
    re-fetches them for the backward (the cotangent of the fetch is a
    device→host copy, landing gradients host-side).

A model opts in one of two ways:

  1. TransformerLM family: ``config.param_host_offload`` (the engine
     flips it on and the model's own scan streams — models/
     transformer.py:505).
  2. Any other model: expose ``host_param_paths`` — an iterable of
     top-level parameter-tree keys whose leaves are ``[L, ...]`` stacks.
     The engine pins those subtrees and sets
     ``model.param_host_offload = True``; the model consults that flag
     in ``apply`` and wraps its layer scan in :func:`scan_streamed`.

See tests/test_offload.py::test_offload_param_protocol_custom_model for
a complete non-TransformerLM example.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.utils import memspace


def fetch_slice(stacked_tree: Any, i) -> Any:
    """Fetch layer ``i`` of a host-pinned ``[L, ...]`` stacked tree to
    device memory. Usable inside jit/scan bodies; under remat the
    backward replay re-issues the copy instead of saving the layer."""
    return jax.tree.map(
        lambda a: memspace.put(
            lax.dynamic_index_in_dim(a, i, keepdims=False), "device"),
        stacked_tree)


def pin_stage(anchor: Any, pinned: Any):
    """Explicit sequencing for the overlap engine: tie the in-flight
    transfer values ``pinned`` (h2d layer fetches, d2h grad streams,
    fsdp gathers) and the stage's compute ``anchor`` into one scheduling
    stage via ``lax.optimization_barrier``.

    Identity on every value — the barrier only forbids the scheduler
    from sinking a transfer issued in stage ``i`` toward the stage that
    consumes it (where it would land on the critical path) or hoisting
    later compute above it. ``tools/latency_hiding_probe.py`` measured
    that XLA's own latency-hiding pass does NOT keep these copies off
    the critical path in the default scan schedule on v5e-1; pinning
    the issue order into the program is the control that works on every
    backend. Callers keep it inside custom-VJP fwd/bwd bodies
    (streamed_layers_prefetch does), never in a differentiated trace.
    """
    return lax.optimization_barrier((anchor, pinned))


def scan_streamed(body: Callable[[Any, Any], Any], carry: Any,
                  stacked_tree: Any, *, length: Optional[int] = None,
                  remat: bool = True,
                  remat_policy: Optional[str] = None) -> Any:
    """``lax.scan`` over a host-pinned layer stack, fetching one slice
    per step inside the (optionally rematerialized) body.

    body(carry, layer_params) -> carry. Returns the final carry.
    ``remat=True`` is required for the memory win: without it every
    fetched layer would be saved as a backward residual and the full
    stack would materialize in HBM anyway.
    """
    if length is None:
        length = jax.tree.leaves(stacked_tree)[0].shape[0]

    def fetched(carry, i):
        return body(carry, fetch_slice(stacked_tree, i))

    if remat:
        from deepspeed_tpu.runtime.activation_checkpointing import \
            checkpoint_wrapper

        fetched = checkpoint_wrapper(fetched, policy=remat_policy)

    def scan_body(carry, i):
        return fetched(carry, i), None

    carry, _ = lax.scan(scan_body, carry, jnp.arange(length))
    return carry


def streamed_layers_prefetch(layer_fn: Callable[..., Any],
                             stacked_tree: Any, x: Any,
                             length: Optional[int] = None,
                             extra: tuple = (),
                             prefetch_depth: int = 1,
                             grads_to_host: bool = True,
                             overlap_depth: int = 0,
                             fetch: Optional[Callable[[Any, Any], Any]]
                             = None,
                             grad_sink: Optional[Callable[[Any], Any]]
                             = None) -> Any:
    """Double-buffered ZeRO-Infinity layer streaming with EXPLICIT
    prefetch — the DeepCompile-prefetch analog (reference
    deepspeed/compile/passes/prefetch.py and the round-3/4 claim that
    XLA's scheduler would hide the fetches, which measurement refuted:
    on v5e-1 the default scan's host→device layer fetches overlap
    compute not at all — tools/latency_hiding_probe.py measured the
    barrier-serialized program *faster* than XLA's default schedule,
    while compute-only is ~1.7x faster than either).

    Structure: the forward scan carries (x, params_of_layer_i); each
    step issues the fetch of layer i+1 FIRST (data-independent of this
    layer's compute, so the DMA overlaps the layer's matmuls) and saves
    only the layer-input activations. The custom VJP runs the mirrored
    reverse pipeline — fetch layer i-1 while recomputing+differentiating
    layer i — and lands each layer's parameter cotangent in host memory
    (`lax.scan(reverse=True)` stacks them in forward layout). Per-layer
    recompute == the nothing_saveable remat policy; HBM holds at most
    two fp32 layers (current + inflight) plus one layer's transient
    grads.

    layer_fn(x, layer_params, *extra) -> x, differentiable in (x,
    layer_params); ``extra`` carries traced non-differentiable values
    the layer needs (e.g. rope positions) — they must be threaded
    explicitly because a custom-vjp backward cannot close over tracers
    from the primal trace. Requires a host-resident ``[L, ...]``
    stacked tree (pin_to_host).

    ``prefetch_depth`` layers ride in flight ahead of the compute (depth
    2 absorbs fetch-time jitter a single buffer exposes; HBM cost is one
    extra fp32 layer). ``grads_to_host=True`` streams each layer's
    parameter cotangent to pinned host memory INSIDE the backward scan —
    the d2h copy of layer i's grads overlaps layer i-1's recompute, and
    the [L, ...] fp32 gradient stack never materializes in HBM (it lands
    where the offload tier's host optimizer reads it anyway). Reference
    analog: the overlapped grad offload of zenflow/superoffload
    (zenflow_stage_1_and_2.py) and DeepCompile's offload_adam_states
    passes.

    ``overlap_depth`` arms the per-layer overlap engine: the K newest
    in-flight transfers — the h2d fetches riding ahead of the forward,
    plus the h2d fetch AND the per-layer grad stream in the backward —
    are pinned into the issuing layer's scheduling stage with
    :func:`pin_stage` (an optimization barrier on the scan carry), so
    the transfer provably issues while that layer computes instead of
    drifting to wherever XLA's scheduler parks it (measured: on v5e-1
    the default schedule hides none of it — the probe's
    barrier-serialized control ran *faster* than XLA's own order).
    0 (default) emits today's program bit-for-bit, barrier-free; any K
    is identity on values — only the schedule changes.

    ``fetch`` overrides the per-layer fetch (default
    :func:`fetch_slice`, the ZeRO-Infinity h2d copy); the stage-3 path
    passes ``runtime/sharding.py::fsdp_gather_slice`` so the same
    engine staged-carries per-layer fsdp all-gathers. ``grad_sink``
    overrides the per-layer cotangent landing (default: pinned-host put
    when ``grads_to_host``); the stage-3 path passes
    ``fsdp_scatter_grads`` so each layer's grad reduce-scatter issues
    inside the backward scan, overlapping the previous layer's
    recompute.
    """
    import numpy as np

    if length is None:
        length = jax.tree.leaves(stacked_tree)[0].shape[0]
    L = length
    D = max(1, min(int(prefetch_depth), L))
    K = max(0, min(int(overlap_depth), D))
    fetch = fetch_slice if fetch is None else fetch

    if grad_sink is None and grads_to_host:
        def grad_sink(dp):
            # per-layer d2h INSIDE the scan: overlaps the next layer's
            # recompute, and the stacked cotangent lives in host memory
            # (matching the host-pinned primal stack)
            return jax.tree.map(
                lambda a: memspace.put(a, "pinned_host"), dp)

    @jax.custom_vjp
    def run(stack, x, extra):
        y, _ = _fwd(stack, x, extra)
        return y

    def _fwd(stack, x, extra):
        bufs = tuple(fetch(stack, i) for i in range(D))

        def body(carry, i):
            x, bufs = carry
            # prefetch BEFORE compute: the copy has no data dependence
            # on this layer's output, so it can ride the DMA engine
            # while the MXU runs layer i
            nxt = fetch(stack, jnp.minimum(i + D, L - 1))
            y = layer_fn(x, bufs[0], *extra)
            bufs = bufs[1:] + (nxt,)
            if K:
                # overlap engine: pin the K newest in-flight fetches
                # into THIS stage — issued alongside layer i's compute,
                # not sunk toward the layer that consumes them
                y, pinned = pin_stage(y, bufs[D - K:])
                bufs = bufs[:D - K] + tuple(pinned)
            return (y, bufs), x  # save the layer INPUT

        (y, _), xs = lax.scan(body, (x, bufs), jnp.arange(L))
        return y, xs

    def run_fwd(stack, x, extra):
        y, xs = _fwd(stack, x, extra)
        return y, (stack, xs, extra)

    def run_bwd(res, g):
        stack, xs, extra = res
        bufs = tuple(fetch(stack, max(L - 1 - i, 0))
                     for i in range(D))

        def body(carry, i):
            gy, bufs = carry  # bufs[0] = params of layer i
            prv = fetch(stack, jnp.maximum(i - D, 0))
            _, vjp_fn = jax.vjp(
                lambda xx, pp: layer_fn(xx, pp, *extra), xs[i], bufs[0])
            dx, dp = vjp_fn(gy)
            if grad_sink is not None:
                dp = grad_sink(dp)
            bufs = bufs[1:] + (prv,)
            if K:
                # pin layer i's grad stream (d2h / reduce-scatter) and
                # the K newest in-flight fetches into this stage: both
                # overlap this layer's recompute instead of queueing at
                # the scan epilogue behind L layers of compute
                dx, (pinned, dp) = pin_stage(dx, (bufs[D - K:], dp))
                bufs = bufs[:D - K] + tuple(pinned)
            return (dx, bufs), dp

        # reverse=True: iterate L-1..0, outputs stacked in FORWARD
        # layout — the cotangent tree matches the stack with no flip
        (gx, _), dstack = lax.scan(body, (g, bufs), jnp.arange(L),
                                   reverse=True)
        dextra = jax.tree.map(
            lambda a: np.zeros(np.shape(a), jax.dtypes.float0), extra)
        return dstack, gx, dextra

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_tree, x, tuple(extra))


def resolve_gather_ahead(explicit: Optional[int], *, streams_layers: bool,
                         mesh_shape, param_offload: bool) -> Tuple[int, str]:
    """``(depth, reason)``: how many layers ahead a step on an fsdp mesh
    gathers the parameter slices in the layer scan's carry (0: the plain
    scan), from what the engine can observe of the job. A job that names
    no depth gets the plain scan: on the chip the carried gathers cost
    more than they hide (each carried layer is copied once an iteration,
    and the compiler already issues the plain scan's gathers beside the
    layer's products: PERF.md, PR 44), so the depth is a user's to name
    (``performance.overlap_depth``), never a default."""
    if not streams_layers:
        return 0, "the model's layer stack does not run through the streamer"
    if mesh_shape.get("pp", 1) > 1:
        return 0, "pipeline axis"
    if param_offload:
        return 0, "parameter host offload streams the layers itself"
    if mesh_shape.get("fsdp", 1) <= 1:
        return 0, "no fsdp axis"
    if explicit:
        return int(explicit), f"overlap_depth {int(explicit)} named by the job"
    if explicit is not None:
        return 0, "overlap_depth 0 named by the job"
    return 0, ("no depth named: carried gathers measured slower than the "
               "plain scan on the chip")


def export_layer_schedule(depth: int, reason: str, compiler_options) -> None:
    """Publish what the engine chose for the layer stack's collectives
    to the observability hub, at trace time (once a compiled program), as
    ops/attention.py does a kernel choice; never instantiates a hub of
    its own. ``train.layer_gather_ahead``: the carried depth in force (0:
    the plain scan), with the reason as an event;
    ``train.reduce_scatter_windowed``: 0 where the gradient
    reduce-scatter was taken out of the compiler's windowed form
    (runtime/engine.py zero3_compiler_options), else 1."""
    try:
        from deepspeed_tpu.observability.hub import peek_hub

        hub = peek_hub()
    except Exception:
        hub = None
    if hub is None:
        return
    hub.gauge("train.layer_gather_ahead", float(depth))
    hub.record_event("layer_gather_ahead", depth=int(depth), reason=reason)
    windowed = compiler_options.get(
        "xla_tpu_enable_windowed_einsum_for_reduce_scatter", True)
    hub.gauge("train.reduce_scatter_windowed", float(bool(windowed)))
    if compiler_options:
        hub.record_event("train_step_compiler_options", **compiler_options)


def pin_to_host(tree: Any) -> Any:
    """Place a parameter subtree in pinned host memory, staged fp32
    (sub-32-bit host→device streaming is unsupported on current TPU
    runtimes; fp32 is the master precision anyway)."""
    def pin(a):
        if memspace.is_on_host(a) and a.dtype == jnp.float32:
            return a  # already staged (init pins the fp32 masters)
        return jax.device_put(
            a.astype(jnp.float32),
            memspace.with_memory_kind(a.sharding, "pinned_host"))

    return jax.tree.map(pin, tree)
