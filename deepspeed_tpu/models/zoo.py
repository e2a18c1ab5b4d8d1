"""Model zoo presets.

Named configurations, by the class that runs them:

* ``TransformerLM`` (models/transformer.py), one dense block: GPT-2 sizes,
  Llama-2/3, Mistral, Qwen2, Phi-3, OPT, Falcon — the families the reference
  ships policies for (module_inject/containers/*,
  inference/v2/model_implementations/*) — and Ouro, the same block with
  post-branch norms run several times a token (``ut_steps``);
* ``MoETransformerLM`` (models/moe_transformer.py), the same attention with
  a plain softmax top-k expert layer: Mixtral, Qwen2-MoE;
* ``HybridLM`` (models/hybrid.py), recurrent layers among full-attention
  ones in a listed order: Qwen3-Next (periods of gated-DeltaNet layers and
  one gated-attention layer, each with a shared-expert MoE that may hold a
  share of the routed experts) and MiniCPM-SALA (lightning attention among
  block-sparse attention, a dense feed-forward, muP scalings).

``get_model`` finds the class by the configuration's type
(``_model_classes``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM


def _gpt2(h, L, heads, vocab=50257, ctx=1024):
    return TransformerConfig(
        vocab_size=vocab, hidden_size=h, num_layers=L, num_heads=heads,
        max_seq_len=ctx, pos_emb="learned", norm="layernorm",
        activation="gelu_tanh", tie_embeddings=True)


def _llama(h, L, heads, kv_heads, ffn, vocab=128256, ctx=8192,
           theta=500000.0):
    return TransformerConfig(
        vocab_size=vocab, hidden_size=h, num_layers=L, num_heads=heads,
        num_kv_heads=kv_heads, ffn_size=ffn, max_seq_len=ctx, pos_emb="rope",
        norm="rmsnorm", activation="swiglu", tie_embeddings=False,
        rope_theta=theta, norm_eps=1e-5)


CONFIGS = {
    # GPT-2 family (reference policy: module_inject/containers/gpt2.py)
    "gpt2-125m": _gpt2(768, 12, 12),
    "gpt2-350m": _gpt2(1024, 24, 16),
    "gpt2-1.3b": _gpt2(2048, 24, 16),
    # Llama-3 family (reference: inference/v2/model_implementations/llama_v2,
    # module_inject/containers/llama.py)
    "llama3-8b": _llama(4096, 32, 32, 8, 14336),
    "llama3-70b": _llama(8192, 80, 64, 8, 28672),
    # Llama-2 (32k vocab, theta 1e4)
    "llama2-7b": _llama(4096, 32, 32, 32, 11008, vocab=32000, ctx=4096,
                        theta=10000.0),
    # Mistral-7B (reference: inference/v2/model_implementations/mistral)
    "mistral-7b": _llama(4096, 32, 32, 8, 14336, vocab=32000, ctx=8192,
                         theta=10000.0),
    # Qwen2-7B (reference: inference/v2/model_implementations/qwen_v2)
    "qwen2-7b": _llama(3584, 28, 28, 4, 18944, vocab=152064, ctx=8192,
                       theta=1000000.0),
    # Phi-3-mini (reference: inference/v2/model_implementations/phi)
    "phi3-mini": _llama(3072, 32, 32, 32, 8192, vocab=32064, ctx=4096,
                        theta=10000.0),
    # OPT family (reference: inference/v2/model_implementations/opt,
    # module_inject/containers/opt.py): learned positions, ReLU MLP
    "opt-1.3b": TransformerConfig(
        vocab_size=50272, hidden_size=2048, num_layers=24, num_heads=32,
        ffn_size=8192, max_seq_len=2048, pos_emb="learned",
        norm="layernorm", activation="relu", tie_embeddings=True),
    "opt-6.7b": TransformerConfig(
        vocab_size=50272, hidden_size=4096, num_layers=32, num_heads=32,
        ffn_size=16384, max_seq_len=2048, pos_emb="learned",
        norm="layernorm", activation="relu", tie_embeddings=True),
    # Falcon-7B (reference: .../falcon): rope + LayerNorm + GELU MLP +
    # multi-query attention (1 KV head). Deviation: residual blocks are
    # sequential here, not Falcon's fused parallel attn/mlp.
    "falcon-7b": TransformerConfig(
        vocab_size=65024, hidden_size=4544, num_layers=32, num_heads=71,
        num_kv_heads=1, ffn_size=18176, max_seq_len=2048, pos_emb="rope",
        norm="layernorm", activation="gelu", tie_embeddings=True,
        rope_theta=10000.0),
    # tiny debug config (reference tests/unit/simple_model.py role)
    "tiny": TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2,
                              num_heads=4, max_seq_len=128, remat=False),
    # Ouro-2.6B (ByteDance/Ouro-2.6B config.json, modeling_ouro.py): a
    # looped stack. 48 Llama-style layers (multi-head attention, 16 of 16
    # heads of 128, rotary on the whole head; SwiGLU) with a norm after each
    # branch as well as before it, run four times a token: the model's one
    # final norm after every pass, each pass's keys and values kept apart
    # (192 K/V slots a token from 48 layers of weights), and an exit gate
    # whose threshold 1 lets no token leave before the last pass.
    "ouro-2.6b": TransformerConfig(
        vocab_size=49152, hidden_size=2048, num_layers=48, num_heads=16,
        num_kv_heads=16, ffn_size=5632, max_seq_len=65536, pos_emb="rope",
        norm="rmsnorm", activation="swiglu", tie_embeddings=False,
        rope_theta=1000000.0, norm_eps=1e-6, post_norms=True, ut_steps=4,
        early_exit_threshold=1.0),
    # the same stack at a toy size: three layers, four passes
    "tiny-ouro": TransformerConfig(
        vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
        num_kv_heads=4, ffn_size=128, max_seq_len=256, pos_emb="rope",
        norm="rmsnorm", activation="swiglu", tie_embeddings=False,
        rope_theta=1000000.0, norm_eps=1e-6, post_norms=True, ut_steps=4,
        early_exit_threshold=1.0, remat=False),
}


def _register_moe():
    from deepspeed_tpu.models.moe_transformer import MoETransformerConfig

    def _moe(h, L, heads, kv, ffn, E, k, vocab, ctx, theta):
        return MoETransformerConfig(
            vocab_size=vocab, hidden_size=h, num_layers=L, num_heads=heads,
            num_kv_heads=kv, ffn_size=ffn, max_seq_len=ctx, pos_emb="rope",
            norm="rmsnorm", activation="swiglu", tie_embeddings=False,
            rope_theta=theta, num_experts=E, top_k=k)

    CONFIGS.update({
        # Mixtral-8x7B (reference: inference/v2/model_implementations/mixtral)
        "mixtral-8x7b": _moe(4096, 32, 32, 8, 14336, E=8, k=2,
                             vocab=32000, ctx=32768, theta=1000000.0),
        # Qwen2-MoE-A14B-style (reference: .../qwen_v2_moe)
        "qwen2-moe-a14b": _moe(3584, 28, 28, 4, 2560, E=64, k=8,
                               vocab=151936, ctx=8192, theta=1000000.0),
        "tiny-moe": MoETransformerConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=128, pos_emb="rope", norm="rmsnorm",
            activation="swiglu", remat=False, num_experts=4, top_k=2),
    })


_register_moe()


def _register_hybrid():
    from deepspeed_tpu.models.hybrid import HybridConfig

    CONFIGS.update({
        # Qwen3-Next-80B-A3B (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct
        # config.json, model_type qwen3_next) at the published values
        "qwen3-next-80b-a3b": HybridConfig(
            vocab_size=151936, hidden_size=2048, num_layers=48, num_heads=16,
            num_kv_heads=2, attn_head_dim=256, max_seq_len=262144,
            pos_emb="rope", norm="rmsnorm", activation="swiglu",
            tie_embeddings=False, rope_theta=1e7, norm_eps=1e-6,
            partial_rotary_factor=0.25, full_attention_interval=4,
            linear_num_key_heads=16, linear_num_value_heads=32,
            linear_key_head_dim=128, linear_value_head_dim=128,
            linear_conv_kernel_dim=4, num_experts=512, top_k=10,
            moe_ffn_size=512, shared_ffn_size=512),
        "tiny-hybrid": HybridConfig(
            vocab_size=256, hidden_size=64, num_layers=8, num_heads=4,
            num_kv_heads=2, attn_head_dim=32, max_seq_len=256,
            pos_emb="rope", norm="rmsnorm", activation="swiglu",
            tie_embeddings=False, rope_theta=1e4, norm_eps=1e-6,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=32, linear_value_head_dim=32,
            num_experts=16, top_k=4, moe_ffn_size=32, shared_ffn_size=32,
            experts_held=4, remat=False),
        # MiniCPM-SALA (huggingface.co/openbmb/MiniCPM-SALA config.json,
        # model_type minicpm_sala) at the published values: 8 block-sparse
        # softmax layers ("m", no rotary) among 24 lightning-attention
        # layers ("l"), a dense SwiGLU under each, muP scalings. The seven
        # sparse sizes are the MiniCPM4 family's sparse_config.
        "minicpm-sala": HybridConfig(
            vocab_size=73448, hidden_size=4096, num_layers=32, num_heads=32,
            num_kv_heads=2, attn_head_dim=128, ffn_size=16384,
            max_seq_len=524288, pos_emb="rope", norm="rmsnorm",
            activation="swiglu", tie_embeddings=False, rope_theta=1e4,
            norm_eps=1e-6, partial_rotary_factor=0.0,
            layer_pattern="mllllllllmllllllmmllllmllllllmmm",
            recurrent_kind="lightning", linear_num_key_heads=32,
            linear_num_value_heads=32, linear_key_head_dim=128,
            linear_value_head_dim=128, num_experts=0, scale_emb=12.0,
            residual_scale=1.4 / 32 ** 0.5, logit_divisor=4096 / 256,
            sparse_topk=64, sparse_kernel_size=32, sparse_kernel_stride=16,
            sparse_block_size=64, sparse_init_blocks=1,
            sparse_window_size=2048, sparse_dense_len=8192),
        # the same stack at a toy size, the sparse sizes shrunk so that a
        # prompt of a hundred tokens crosses dense_len
        "tiny-sala": HybridConfig(
            vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, attn_head_dim=32, ffn_size=128, max_seq_len=256,
            pos_emb="rope", norm="rmsnorm", activation="swiglu",
            tie_embeddings=False, rope_theta=1e4, norm_eps=1e-6,
            partial_rotary_factor=0.0, layer_pattern="lmllmlml",
            first_layer=1, recurrent_kind="lightning",
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=32, linear_value_head_dim=32, num_experts=0,
            scale_emb=12.0, residual_scale=1.4 / 8 ** 0.5, logit_divisor=4.0,
            sparse_topk=5, sparse_kernel_size=8, sparse_kernel_stride=4,
            sparse_block_size=16, sparse_init_blocks=1, sparse_window_size=32,
            sparse_dense_len=64, remat=False),
        # Kimi-K2 (huggingface.co/moonshotai/Kimi-K2.7-Code config.json,
        # model_type kimi_k2: DeepSeek-V3's layers) at the published values:
        # multi-head latent attention in all 61 layers, one leading dense
        # layer, then 384 routed experts (top 8 by sigmoid score with a
        # bias that corrects the choice, weights scaled by 2.827) and an
        # ungated shared expert, YaRN over 4096 original positions.
        "kimi-k2": HybridConfig(
            vocab_size=163840, hidden_size=7168, num_layers=61, num_heads=64,
            num_kv_heads=64, attn_head_dim=192, ffn_size=18432,
            max_seq_len=262144, pos_emb="rope", norm="rmsnorm",
            activation="swiglu", tie_embeddings=False, rope_theta=50000.0,
            norm_eps=1e-5, full_attention_interval=1, attention_kind="mla",
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, rope_yarn_factor=64.0,
            rope_original_max=4096, rope_beta_fast=32.0, rope_beta_slow=1.0,
            rope_mscale_all_dim=1.0, first_k_dense=1, num_experts=384,
            top_k=8, moe_ffn_size=2048, shared_ffn_size=2048,
            router_scoring="sigmoid", routed_scale=2.827, shared_gate=False),
        # the same stack at a toy size (YaRN over 32 original positions, so
        # that a prompt of a hundred tokens lies in the interpolated range)
        "tiny-kimi": HybridConfig(
            vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
            num_kv_heads=4, attn_head_dim=48, ffn_size=128, max_seq_len=256,
            pos_emb="rope", norm="rmsnorm", activation="swiglu",
            tie_embeddings=False, rope_theta=1e4, norm_eps=1e-5,
            full_attention_interval=1, attention_kind="mla", q_lora_rank=48,
            kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, rope_yarn_factor=8.0, rope_original_max=32,
            first_k_dense=1, num_experts=16, top_k=4, moe_ffn_size=32,
            shared_ffn_size=32, experts_held=4, router_scoring="sigmoid",
            routed_scale=2.5, shared_gate=False, remat=False),
        # dots3-note-prev (huggingface.co/dots-studio/dots3-note-prev
        # config.json, model_type dots3_note) at the published values: 46
        # layers, a full latent layer then periods of one full and three
        # sliding ones. Full: latent attention (128 heads of 128 + 64 over a
        # 512 + 64 latent) whose context a learned selector picks (64 heads
        # of 128 score every cached token's 128-value key, the top 2,048
        # are attended); sliding: a latent mixer of its own (64 heads of
        # 192 + 64 over a 1024 + 64 latent) over the last 513 tokens, its
        # own rotary base; a sigmoid gate a head on both; both normed
        # latents rescaled. One leading dense layer, then 256 routed
        # experts (top 8 by sigmoid score, a bias in the choice, weights
        # renormalised) and an ungated shared expert.
        "dots3-note": HybridConfig(
            vocab_size=152064, hidden_size=5120, num_layers=46,
            num_heads=128, num_kv_heads=128, attn_head_dim=192,
            ffn_size=13824, max_seq_len=524288, pos_emb="rope",
            norm="rmsnorm", activation="swiglu", tie_embeddings=False,
            rope_theta=8e7, norm_eps=1e-5, attention_kind="mla",
            q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128,
            layer_pattern="m" + "mwww" * 11 + "m", sliding_window=513,
            window_attention_kind="mla", window_num_heads=64,
            window_q_lora_rank=1024, window_kv_lora_rank=1024,
            window_qk_nope_head_dim=192, window_qk_rope_head_dim=64,
            window_v_head_dim=128, window_rope_theta=50000.0,
            mla_lora_rescale=True, mla_head_gate=True, index_topk=2048,
            index_n_heads=64, index_head_dim=128, first_k_dense=1,
            num_experts=256, top_k=8, moe_ffn_size=1536,
            shared_ffn_size=1536, router_scoring="sigmoid",
            routed_scale=1.0, shared_gate=False),
        # the same stack at a toy size, cut as the benchmark's cell is (the
        # leading dense layer and two periods), a window and a selection
        # shorter than a test's sequences, 4 of 16 experts held
        "tiny-dots3": HybridConfig(
            vocab_size=256, hidden_size=64, num_layers=9, num_heads=4,
            num_kv_heads=4, attn_head_dim=48, ffn_size=128, max_seq_len=256,
            pos_emb="rope", norm="rmsnorm", activation="swiglu",
            tie_embeddings=False, rope_theta=8e7, norm_eps=1e-5,
            attention_kind="mla", q_lora_rank=48, kv_lora_rank=128,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            layer_pattern="m" + "mwww" * 3, sliding_window=13,
            window_attention_kind="mla", window_num_heads=2,
            window_q_lora_rank=32, window_kv_lora_rank=128,
            window_qk_nope_head_dim=48, window_qk_rope_head_dim=16,
            window_v_head_dim=32, window_rope_theta=5e4,
            mla_lora_rescale=True, mla_head_gate=True, index_topk=16,
            index_n_heads=4, index_head_dim=32, first_k_dense=1,
            num_experts=16, top_k=4, moe_ffn_size=32, shared_ffn_size=32,
            experts_held=4, router_scoring="sigmoid", routed_scale=1.0,
            shared_gate=False, remat=False),
        # Trinity-Mini (huggingface.co/arcee-ai/Trinity-Mini config.json,
        # model_type afmoe) at the published values: gated softmax
        # attention with QK-norm in all 32 layers, three under a 2048-token
        # window (rotary over the whole head) to one full (no position
        # encoding), a norm before and after each branch, two leading dense
        # layers, then 128 routed experts (top 8 by sigmoid score with a
        # bias that takes part in the choice, weights scaled by 2.826) and
        # an ungated shared expert; the embedding times sqrt(hidden). The
        # training layout: experts over the expert layers alone, the loss
        # in tiles, the bias moved by load_balance_coeff a step.
        "trinity-mini": HybridConfig(
            vocab_size=200192, hidden_size=2048, num_layers=32, num_heads=32,
            num_kv_heads=4, attn_head_dim=128, ffn_size=6144,
            max_seq_len=131072, pos_emb="rope", norm="rmsnorm",
            activation="swiglu", tie_embeddings=False, rope_theta=1e4,
            norm_eps=1e-5, partial_rotary_factor=0.0,
            window_rotary_factor=1.0, layer_pattern="wwwm" * 8,
            sliding_window=2048, post_norms=True, first_k_dense=2,
            num_experts=128, top_k=8, moe_ffn_size=1024,
            shared_ffn_size=1024, router_scoring="sigmoid",
            routed_scale=2.826, shared_gate=False, scale_emb=2048 ** 0.5,
            experts_apart=True, bias_update_rate=0.001, tiled_logits=8),
        # the same stack at a toy size, cut as the benchmark's cell is:
        # from the second leading dense layer on, then one period of expert
        # layers (three windowed, one full), a window shorter than a
        # sequence, 4 of 16 experts held
        "tiny-trinity": HybridConfig(
            vocab_size=256, hidden_size=64, num_layers=5, num_heads=4,
            num_kv_heads=2, attn_head_dim=32, ffn_size=128, max_seq_len=256,
            pos_emb="rope", norm="rmsnorm", activation="swiglu",
            tie_embeddings=False, rope_theta=1e4, norm_eps=1e-5,
            partial_rotary_factor=0.0, window_rotary_factor=1.0,
            layer_pattern="wwwm" * 2, first_layer=1, sliding_window=24,
            post_norms=True, first_k_dense=2, num_experts=16, top_k=2,
            moe_ffn_size=32, shared_ffn_size=32, experts_held=4,
            router_scoring="sigmoid", routed_scale=2.5, shared_gate=False,
            scale_emb=8.0, experts_apart=True, bias_update_rate=0.001,
            tiled_logits=2),
        # MiniMax-M3 (huggingface.co/MiniMaxAI/MiniMax-M3 config.json) at
        # the published values: 60 layers of softmax attention (64 query and
        # 4 KV heads of 128, a QK-norm gain a head, rotary over 64 of the
        # 128 dims, no output gate) whose context a learned block selector
        # picks in every layer (4 indexer heads a KV group over 128-token
        # blocks' max-pooled indexer keys: the 16 best blocks, the first and
        # the local two); three leading dense layers, then 128 routed
        # experts (top 4 by sigmoid score with a bias in the choice, weights
        # renormalised and scaled by 2) and an ungated shared expert; the
        # clamped swigluoai activation in every feed-forward. The selector's
        # projections and their width are the benchmark configuration's
        # ``assumed``.
        "minimax-m3": HybridConfig(
            vocab_size=200064, hidden_size=6144, num_layers=60, num_heads=64,
            num_kv_heads=4, attn_head_dim=128, ffn_size=12288,
            max_seq_len=1048576, pos_emb="rope", norm="rmsnorm",
            activation="swigluoai", swiglu_alpha=1.702, swiglu_limit=7.0,
            tie_embeddings=False, rope_theta=5e6, norm_eps=1e-6,
            partial_rotary_factor=0.5, layer_pattern="m" * 60,
            attn_output_gate=False, qk_norm_per_head=True, msa_topk=16,
            msa_block_size=128, msa_index_heads=4, msa_index_dim=128,
            msa_local_blocks=2, first_k_dense=3, num_experts=128, top_k=4,
            moe_ffn_size=3072, shared_ffn_size=3072,
            router_scoring="sigmoid", routed_scale=2.0, shared_gate=False),
        # the same stack at a toy size, cut as the benchmark's cell is (the
        # last leading dense layer and expert layers behind it), blocks of 8
        # and the 2 best, so that a prompt of fifty tokens is selected from;
        # an eighth of 16 experts held
        "tiny-m3": HybridConfig(
            vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
            num_kv_heads=2, attn_head_dim=32, ffn_size=128, max_seq_len=256,
            pos_emb="rope", norm="rmsnorm", activation="swigluoai",
            tie_embeddings=False, rope_theta=5e6, norm_eps=1e-6,
            partial_rotary_factor=0.5, layer_pattern="m" * 6, first_layer=2,
            attn_output_gate=False, qk_norm_per_head=True, msa_topk=2,
            msa_block_size=8, msa_index_heads=2, msa_index_dim=16,
            msa_local_blocks=2, first_k_dense=3, num_experts=16, top_k=4,
            moe_ffn_size=32, shared_ffn_size=32, experts_held=2,
            router_scoring="sigmoid", routed_scale=2.0, shared_gate=False,
            remat=False),
        # NVIDIA-Nemotron-3-Nano-30B-A3B (huggingface.co/nvidia/
        # NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json, model_type
        # nemotron_h) at the published values: 52 blocks of one mixer each
        # by ``hybrid_override_pattern`` (23 Mamba-2 "M": 64 heads of 64,
        # 8 groups of B / C, state 128, a biased convolution of 4 taps,
        # chunks of 128, a gated norm over groups of 512 channels; 23
        # expert blocks "E": top 6 of 128 by sigmoid score with a bias in
        # the choice, weights renormalised and scaled by 2.5, ungated
        # squared-ReLU experts 1,856 wide and a shared one 3,712 wide; 6
        # attention blocks "*": 32 query and 2 KV heads of 128 with no
        # position encoding, no QK-norm and no gate). The training layout:
        # the loss in tiles, the bias moved 0.001 a step.
        "nemotron3-nano": HybridConfig(
            vocab_size=131072, hidden_size=2688, num_layers=52, num_heads=32,
            num_kv_heads=2, attn_head_dim=128, ffn_size=1856,
            max_seq_len=262144, pos_emb="rope", norm="rmsnorm",
            activation="relu2", tie_embeddings=False, rope_theta=1e4,
            norm_eps=1e-5, partial_rotary_factor=0.0,
            layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                          "EMEMEMEME",
            attn_output_gate=False, qk_norm=False, recurrent_kind="mamba2",
            mamba_num_heads=64, mamba_head_dim=64, mamba_n_groups=8,
            mamba_state_size=128, mamba_conv_kernel=4, mamba_chunk=128,
            num_experts=128, top_k=6, moe_ffn_size=1856,
            shared_ffn_size=3712, router_scoring="sigmoid", routed_scale=2.5,
            shared_gate=False, bias_update_rate=0.001, tiled_logits=8),
        # the same stack at a toy size: the pattern's three kinds of block
        # (an "EMEM*" of the toy pattern from block 1 on), 2 groups, 16
        # router outputs of which 4 experts are held, chunks of 16
        "tiny-nemotron": HybridConfig(
            vocab_size=256, hidden_size=64, num_layers=5, num_heads=4,
            num_kv_heads=2, attn_head_dim=32, ffn_size=32, max_seq_len=256,
            pos_emb="rope", norm="rmsnorm", activation="relu2",
            tie_embeddings=False, rope_theta=1e4, norm_eps=1e-5,
            partial_rotary_factor=0.0, layer_pattern="MEMEM*EME",
            first_layer=1, attn_output_gate=False, qk_norm=False,
            recurrent_kind="mamba2", mamba_num_heads=4, mamba_head_dim=16,
            mamba_n_groups=2, mamba_state_size=16, mamba_conv_kernel=4,
            mamba_chunk=16, num_experts=16, top_k=2, moe_ffn_size=32,
            shared_ffn_size=64, experts_held=4, router_scoring="sigmoid",
            routed_scale=2.5, shared_gate=False, bias_update_rate=0.001,
            tiled_logits=2),
    })


_register_hybrid()


def _model_classes():
    """Configuration type -> model class, most derived first."""
    from deepspeed_tpu.models.hybrid import HybridConfig, HybridLM
    from deepspeed_tpu.models.moe_transformer import (
        MoETransformerConfig, MoETransformerLM)

    return ((HybridConfig, HybridLM),
            (MoETransformerConfig, MoETransformerLM),
            (TransformerConfig, TransformerLM))


def get_model(name: str, **overrides) -> TransformerLM:
    """Instantiate a preset, optionally overriding config fields
    (e.g. max_seq_len, remat_policy, sequence_parallel)."""
    if name not in CONFIGS:
        raise ValueError(f"unknown model '{name}'; known: {sorted(CONFIGS)}")
    cfg = CONFIGS[name]
    # env-derived fields resolve at __post_init__; presets were built at
    # import, so re-resolve here (set to None → replace re-runs
    # __post_init__) or a later DSTPU_PREFETCH/DSTPU_SERIALIZE_FETCH
    # flip would be silently ignored for zoo models
    env_fields = {f: None for f in ("prefetch_stream", "serialize_fetch",
                                    "prefetch_depth", "grads_to_host",
                                    "overlap_depth")
                  if f not in overrides}
    cfg = dataclasses.replace(cfg, **env_fields, **overrides)
    return next(model for kind, model in _model_classes()
                if isinstance(cfg, kind))(cfg)
