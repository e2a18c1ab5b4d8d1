"""v1-style inference engine: jit + TP sharding + dense KV cache.

Reference: ``InferenceEngine`` (inference/engine.py:40) swaps HF blocks for
fused CUDA kernels (``replace_transformer_layer``
module_inject/replace_module.py:189), shards weights over a model-parallel
group, and optionally captures CUDA graphs (:497).

TPU re-design: no layer surgery — the model's logical axes already name
every shardable dim, so "kernel injection + TP" collapses to placing the
param tree with a tensor-parallel NamedSharding and jitting
prefill/decode. jit caching per shape bucket is the CUDA-graph analog.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.inference import model_runner
from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.parallel import topology as topo
from deepspeed_tpu.runtime.sharding import spec_from_logical
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
from deepspeed_tpu.utils.logging import log_dist, logger

# TP rule table for inference (reference AutoTP policy: qkv/mlp-in column,
# o/mlp-out row — module_inject/auto_tp.py:194; here one rule table)
TP_PARAM_RULES = (
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("mlp", "tp"),
    ("vocab", "tp"),
)


class InferenceEngine:
    """Generate-capable engine over a TransformerLM.

    API parity with the reference: ``forward`` (logits), ``generate``;
    ``tp_size`` via the mesh's tp axis.
    """

    def __init__(self, model: TransformerLM, mesh: Optional[Mesh] = None,
                 params: Optional[Dict[str, Any]] = None,
                 dtype=jnp.bfloat16, max_batch: int = 8,
                 max_seq_len: Optional[int] = None, seed: int = 0,
                 quantize_weights: Optional[str] = None):
        enable_compile_cache()
        self.model = model
        self.cfg = model.config
        if mesh is None:
            mesh = topo._GLOBAL_MESH or topo.build_mesh(
                topo.TopologyConfig(dp=-1))
        self.mesh = mesh
        tp = mesh.shape.get("tp", 1)
        for name, heads in (("num_heads", self.cfg.num_heads),
                            ("kv_heads", self.cfg.kv_heads)):
            if heads % tp:
                raise ValueError(
                    f"tp={tp} does not divide {name}={heads}: the TP "
                    "placement shards the head axes evenly (reference "
                    "AutoTP has the same constraint); lower tp or use "
                    "a model whose head counts divide")
        if quantize_weights is not None and quantize_weights != "int8":
            raise ValueError(
                f"quantize_weights supports 'int8', got "
                f"{quantize_weights!r}")
        if quantize_weights is not None and tp > 1:
            raise ValueError(
                "quantize_weights does not compose with tp>1 yet "
                "(blockwise payloads have an extra rank the TP "
                "specs don't cover); serve unquantized or tp=1")
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len or self.cfg.max_seq_len
        self._dtype = dtype

        # (a model may take its tree in more than one layout: models/hybrid.py)
        axes = model.axes_for(params) if params is not None and hasattr(
            model, "axes_for") else model.logical_axes()
        self._param_specs = jax.tree.map(
            lambda la: spec_from_logical(la, TP_PARAM_RULES), axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._param_specs)
        if params is None:
            with self.mesh:
                params = jax.jit(
                    model.init, out_shardings=shardings)(
                        jax.random.PRNGKey(seed))
        else:
            params = jax.device_put(params, shardings)
        if quantize_weights is not None:
            # weight-only int8 serving (reference MoQ/GroupQuantizer,
            # module_inject/replace_module.py:44): HBM holds ~4x less
            # weight; dequant happens lazily at each use inside the
            # compiled step (inference/weight_quant.py). Arg validation
            # ran before model materialization.
            from deepspeed_tpu.inference.weight_quant import (
                quantize_params, quantized_fraction)

            params = quantize_params(params)
            log_dist(
                f"weight-only int8 serving: "
                f"{quantized_fraction(params):.0%} of weight bytes "
                "quantized", ranks=[0])
        self.params = params

        # jit caches per input shape, so one function serves every
        # (prefill-bucket, decode) composition — the CUDA-graph analog
        self._step = jax.jit(partial(model_runner.forward_with_cache, self.cfg))
        log_dist(
            f"InferenceEngine: tp={self.mesh.shape.get('tp', 1)} "
            f"max_batch={max_batch} max_seq_len={self.max_seq_len}", ranks=[0])

    # -- API --------------------------------------------------------------

    def forward(self, tokens) -> jax.Array:
        """Full-sequence logits (no cache) — parity with reference
        InferenceEngine.forward (inference/engine.py:557)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        with self.mesh:
            return self.model.apply(self.params, tokens)

    __call__ = forward

    def generate(self, tokens, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 seed: int = 0, eos_token_id: Optional[int] = None):
        """Greedy/top-k sampling with a dense KV cache.

        tokens: [B, S] prompt (list/np/jnp). Returns np.ndarray
        [B, S + max_new_tokens] (right-padded with eos if a row stops
        early).
        """
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        B, S = tokens.shape
        assert B <= self.max_batch, f"batch {B} > max_batch {self.max_batch}"
        total = S + max_new_tokens
        assert total <= self.max_seq_len, "prompt + new tokens > max_seq_len"

        # bucket the prompt length to bound compilations
        bucket = max(16, 1 << (S - 1).bit_length())
        bucket = min(bucket, self.max_seq_len)
        padded = np.zeros((B, bucket), np.int32)
        padded[:, :S] = tokens

        cache = model_runner.init_dense_cache(
            self.cfg, B, self.max_seq_len, self._dtype)
        with self.mesh:
            logits, cache = self._step(
                self.params, jnp.asarray(padded), cache, 0)
        # NOTE: positions beyond S wrote garbage rows into the cache, but
        # decode masks keys by position <= query pos and we overwrite row
        # S first, so only rows < S are ever attended.
        next_logits = logits[:, S - 1]  # [B, V]

        rng = jax.random.PRNGKey(seed)
        out = [tokens]
        done = np.zeros(B, bool)
        cur_pos = S
        for step in range(max_new_tokens):
            rng, sub = jax.random.split(rng)
            nxt = _sample(next_logits, temperature, top_k, sub)  # [B]
            nxt_np = np.asarray(nxt)
            if eos_token_id is not None:
                nxt_np = np.where(done, eos_token_id, nxt_np)
                done |= nxt_np == eos_token_id
            out.append(nxt_np[:, None].astype(np.int32))
            if eos_token_id is not None and done.all():
                break
            with self.mesh:
                logits, cache = self._step(
                    self.params, jnp.asarray(nxt_np[:, None], jnp.int32),
                    cache, cur_pos)
            next_logits = logits[:, 0]
            cur_pos += 1

        result = np.concatenate(out, axis=1)
        if result.shape[1] < total and eos_token_id is not None:
            pad = np.full((B, total - result.shape[1]), eos_token_id, np.int32)
            result = np.concatenate([result, pad], axis=1)
        return result


def _sample(logits, temperature, top_k, rng):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def init_inference(model=None, tensor_parallel: Optional[Dict] = None,
                   dtype=jnp.bfloat16, max_batch: int = 8,
                   max_seq_len: Optional[int] = None,
                   mesh: Optional[Mesh] = None, params=None,
                   **kwargs) -> InferenceEngine:
    """Reference ``deepspeed.init_inference`` (__init__.py:328) analog.

    model: a TransformerLM or a model-zoo name (str).
    tensor_parallel: {"tp_size": N} — builds a tp mesh if none given.
    """
    if isinstance(model, str):
        from deepspeed_tpu.models.zoo import get_model

        model = get_model(model)
    tp_size = (tensor_parallel or {}).get("tp_size", 1)
    if mesh is None:
        mesh = topo._GLOBAL_MESH
    if mesh is None:
        mesh = topo.build_mesh(topo.TopologyConfig(dp=-1, tp=tp_size))
    return InferenceEngine(model, mesh=mesh, params=params, dtype=dtype,
                           max_batch=max_batch, max_seq_len=max_seq_len,
                           **kwargs)
