"""Model-zoo preset coverage (reference analog: per-arch containers in
module_inject/containers + inference/v2/model_implementations)."""

import numpy as np
import pytest

import jax
import jax.flatten_util
import jax.numpy as jnp

from deepspeed_tpu.models.zoo import CONFIGS, get_model
from deepspeed_tpu.models.hybrid import HybridConfig
from deepspeed_tpu.models.moe_transformer import MoETransformerConfig

SHRINK = dict(num_layers=2, hidden_size=64, ffn_size=128, num_heads=4,
              num_kv_heads=4, vocab_size=128, max_seq_len=64, remat=False)

DENSE = sorted(n for n, c in CONFIGS.items()
               if not isinstance(c, (MoETransformerConfig, HybridConfig)))
HYBRID = sorted(n for n, c in CONFIGS.items() if isinstance(c, HybridConfig))


@pytest.mark.parametrize("name", DENSE)
def test_every_dense_preset_runs(name, devices):
    model = get_model(name, **SHRINK)
    params = model.init(jax.random.PRNGKey(0))
    out = model.apply(params, jnp.zeros((2, 16), jnp.int32))
    assert out.shape == (2, 16, 128)
    assert np.isfinite(np.asarray(out, np.float32)).all()


@pytest.mark.parametrize("name", HYBRID)
def test_every_hybrid_preset_runs(name, devices):
    """One period (3 recurrent : 1 full) at toy widths, a share of the
    experts held."""
    model = get_model(name, num_layers=4, hidden_size=64, num_heads=4,
                      num_kv_heads=2, attn_head_dim=16, vocab_size=128,
                      max_seq_len=64, remat=False, linear_num_key_heads=2,
                      linear_num_value_heads=4, linear_key_head_dim=16,
                      linear_value_head_dim=16, num_experts=8, top_k=2,
                      moe_ffn_size=32, shared_ffn_size=32, experts_held=4)
    params = model.init(jax.random.PRNGKey(0))
    out = model.apply(params, jnp.zeros((2, 16), jnp.int32))
    assert out.shape == (2, 16, 128)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    loss, _ = model.loss(params, {"input_ids": jnp.ones((2, 9), jnp.int32)})
    assert np.isfinite(float(loss))


def test_relu_activation_distinct(devices):
    gelu = get_model("gpt2-125m", **SHRINK)
    relu = get_model("opt-1.3b", **SHRINK)
    assert gelu.config.activation == "gelu_tanh"  # GPT-2 gelu_new
    assert relu.config.activation == "relu"
    p = relu.init(jax.random.PRNGKey(0))
    g = jax.grad(lambda p: relu.loss(
        p, {"input_ids": jnp.ones((2, 8), jnp.int32)})[0])(p)
    assert np.isfinite(np.asarray(
        jax.flatten_util.ravel_pytree(g)[0], np.float32)).all()


def test_moe_presets_listed():
    assert "mixtral-8x7b" in CONFIGS
    assert "qwen2-moe-a14b" in CONFIGS
