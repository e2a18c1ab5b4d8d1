"""The ``kimi_k2`` family on the CPU at a toy size (``tiny-kimi``): latent
attention's two forms against each other and against the plain reference;
YaRN and the router against numbers worked by hand; the latent pool's
accounting; the engine (chunked prefill over a latent paged pool, absorbed
decode through the ``mla_decode`` kernel in interpret mode) against the full
forward; what cannot hold a latent page refusing by name; the prefix cache
and the host tier over latent pages; the share test."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import hybrid_runner
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.ragged import (BlockedKVCache, KVCacheConfig,
                                            LatentPoolUnsupported)
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.transformer import yarn_inv_freq, yarn_mscale
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops.pallas.paged_attention import mla_decode_attention
from deepspeed_tpu.parallel.moe import (GateConfig, moe_ffn_share, route,
                                        route_top_k)
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

F32 = jnp.float32


def _model(**kw):
    return get_model("tiny-kimi", param_dtype=F32, dtype=F32, **kw)


def _params(model, seed=0):
    """Seeded weights with the bias drawn so that it changes choices."""
    p = model.init(jax.random.PRNGKey(seed))
    moe = p["layers"]["moe"]
    moe["router"] = moe["router"] * 4.0
    moe["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), moe["router_bias"].shape)
    return p


def _engine(model, params, **kw):
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    kw = dict(dict(kv_block_size=16, kv_blocks=64, max_tokens_per_step=32,
                   max_seqs_per_step=4, max_blocks_per_seq=12), **kw)
    return InferenceEngineV2(model, mesh=mesh, params=params, dtype=F32, **kw)


def _reference():
    from benchmarks.harness import manifest as mf

    return mf.load_module("references", "kimi_k2")


def _arch(ref, cfg, held=None, offset=0):
    rs = {"type": "yarn", "factor": cfg.rope_yarn_factor, "mscale": 1,
          "mscale_all_dim": cfg.rope_mscale_all_dim,
          "original_max_position_embeddings": cfg.rope_original_max,
          "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow}
    return ref.Arch.from_model(dict(
        hidden_size=cfg.hidden_size, intermediate_size=cfg.ffn_size,
        moe_intermediate_size=cfg.moe_ffn_size,
        num_attention_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        first_k_dense_replace=cfg.first_k_dense,
        n_routed_experts=cfg.held if held is None else held,
        n_shared_experts=1, num_experts_per_tok=cfg.top_k,
        routed_scaling_factor=cfg.routed_scale, vocab_size=cfg.vocab_size,
        num_hidden_layers=cfg.num_layers, router_outputs=cfg.num_experts,
        expert_offset=offset, rope_scaling=rs, scoring_func="sigmoid",
        n_group=1, topk_group=1, norm_topk_prob=True, topk_method="noaux_tc"))


def _layer_weights(params, l):
    """Layer ``l`` of the stacked tree under the reference's names."""
    lay = jax.tree.map(lambda a: a[l], params["layers"])
    m, moe = lay["mla"], lay["moe"]
    return {"input_layernorm": lay["ln1"]["scale"],
            "post_attention_layernorm": lay["ln2"]["scale"],
            "q_a_proj": m["wqa"], "q_a_layernorm": m["q_norm"],
            "q_b_proj": m["wqb"], "kv_a_proj_with_mqa": m["wkva"],
            "kv_a_layernorm": m["kv_norm"], "kv_b_proj": m["wkvb"],
            "o_proj": m["wo"], "gate": moe["router"],
            "e_score_correction_bias": moe["router_bias"],
            "experts_gate_proj": moe["experts"]["wg"],
            "experts_up_proj": moe["experts"]["wi"],
            "experts_down_proj": moe["experts"]["wo"],
            "shared_gate_proj": moe["shared"]["wg"],
            "shared_up_proj": moe["shared"]["wi"],
            "shared_down_proj": moe["shared"]["wo"]}


# ---------------------------------------------------------------------------
# YaRN and the router, by hand
# ---------------------------------------------------------------------------


def test_yarn_frequencies_at_the_published_values_by_hand():
    """64 rotary dims, base 50000, factor 64 over 4096 positions, beta 32 /
    1: the correction dims are 64 ln(4096 / (2 pi b)) / (2 ln 50000) = 8.91
    and 19.16, so pairs 0-8 keep their frequency, pairs 20-31 are divided by
    64, and pair 14 (half way up the ramp 8..20) is the mean of the two."""
    inv = np.asarray(yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0))
    plain = 50000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], plain[20:] / 64.0, rtol=1e-6)
    np.testing.assert_allclose(inv[14], plain[14] * (0.5 / 64 + 0.5),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[11], plain[11] * (0.25 / 64 + 0.75),
                               rtol=1e-6)
    assert inv[8] == pytest.approx(50000.0 ** -0.25, rel=1e-6)   # 0.06687


def test_yarn_temperature_and_the_softmax_scale_by_hand():
    assert yarn_mscale(64.0, 1.0) == pytest.approx(1.4158883, rel=1e-6)
    assert yarn_mscale(1.0, 1.0) == 1.0
    cfg = get_model("kimi-k2", num_layers=5, experts_held=12,
                    vocab_size=20480).config
    # 192^-1/2 * 1.41589^2
    assert cfg.mla_scale == pytest.approx(0.0721688 * 2.0047397, rel=1e-5)
    assert cfg.latent_dim == 576 and cfg.stack_plan == (4, ((True, 1),))
    assert [cfg.is_dense(l) for l in range(5)] == [True] + [False] * 4


def test_reference_yarn_is_the_programs():
    ref = _reference()
    cfg = _model().config
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inverse_frequencies(_arch(ref, cfg))),
        np.asarray(cfg.mla_inv_freq()), rtol=1e-6)
    assert ref.yarn_scale(_arch(ref, cfg)) == pytest.approx(
        yarn_mscale(cfg.rope_yarn_factor))


# one token, four experts, scores sigmoid(logit): 0.8, 0.7, 0.6, 0.1
_LOGITS = np.log(np.array([0.8, 0.7, 0.6, 0.1]) / (1 - np.array(
    [0.8, 0.7, 0.6, 0.1])))


@pytest.mark.parametrize("bias,chosen,weights", [
    # no bias: the two largest scores, renormalised, times 2.5
    (None, [0, 1], [2.5 * 0.8 / 1.5, 2.5 * 0.7 / 1.5]),
    # a bias that lifts expert 2 over expert 1 changes the choice; the
    # weights are the *scores* 0.8 and 0.6, the bias is in neither
    ([0.0, 0.0, 0.15, 0.0], [0, 2], [2.5 * 0.8 / 1.4, 2.5 * 0.6 / 1.4]),
    # a bias too small to change the order changes nothing at all
    ([0.0, 0.0, 0.05, 0.0], [0, 1], [2.5 * 0.8 / 1.5, 2.5 * 0.7 / 1.5]),
    # a bias can bring in the worst expert: its weight is still its score
    ([0.0, -1.0, -1.0, 0.0], [0, 3], [2.5 * 0.8 / 0.9, 2.5 * 0.1 / 0.9]),
], ids=["no-bias", "bias-changes-choice-not-weight", "small-bias",
        "bias-brings-in-the-worst"])
def test_sigmoid_router_against_a_hand_table(bias, chosen, weights):
    cfg = GateConfig(num_experts=4, top_k=2, scoring="sigmoid",
                     routed_scale=2.5)
    w, idx = route(jnp.ones((1, 1)), jnp.asarray(_LOGITS[None], F32), cfg,
                   None if bias is None else jnp.asarray(bias, F32))
    order = np.argsort(np.asarray(idx[0]))
    assert list(np.asarray(idx[0])[order]) == chosen
    np.testing.assert_allclose(np.asarray(w[0])[order], weights, rtol=1e-5)


def test_softmax_rule_is_unchanged_and_an_unknown_rule_is_refused():
    y = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    wr = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    a = route(y, wr, GateConfig(num_experts=6, top_k=3))
    b = route_top_k(y, wr, 3)
    assert all(np.array_equal(np.asarray(p), np.asarray(q))
               for p, q in zip(a, b))
    with pytest.raises(ValueError, match="scoring"):
        GateConfig(num_experts=6, scoring="tanh")


def test_reference_router_is_the_programs_on_seeded_weights():
    ref = _reference()
    model = _model()
    p = _params(model)
    y = jax.random.normal(jax.random.PRNGKey(3), (40, 64))
    w = _layer_weights(p, 1)
    want_w, want_i = ref.route(_arch(ref, model.config), y, w)
    got_w, got_i = route(y, w["gate"], model.config.gate,
                         w["e_score_correction_bias"])
    assert np.array_equal(np.asarray(want_i), np.asarray(got_i))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=1e-5)
    # the seeded bias does change some choices
    _, plain = route(y, w["gate"], model.config.gate, None)
    moved = np.mean([set(a) != set(b) for a, b in zip(
        np.asarray(plain).tolist(), np.asarray(got_i).tolist())])
    assert 0.0 < moved < 1.0


def test_shared_expert_with_and_without_a_gate():
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    y = jax.random.normal(k[0], (6, 16))
    experts = {"wg": jax.random.normal(k[1], (2, 16, 8)) * 0.2,
               "wi": jax.random.normal(k[2], (2, 16, 8)) * 0.2,
               "wo": jax.random.normal(k[3], (2, 8, 16)) * 0.2}
    shared = {"wg": jax.random.normal(k[4], (16, 8)) * 0.2,
              "wi": jax.random.normal(k[5], (16, 8)) * 0.2,
              "wo": jax.random.normal(k[6], (8, 16)) * 0.2}
    router = jax.random.normal(k[7], (16, 4))
    cfg = GateConfig(num_experts=4, top_k=2, drop_tokens=False)
    none, _ = moe_ffn_share(y, router, experts, cfg)
    plain, _ = moe_ffn_share(y, router, experts, cfg, shared=shared)
    gate = jnp.ones((16,)) * 0.3
    gated, _ = moe_ffn_share(y, router, experts, cfg,
                             shared=dict(shared, gate=gate))
    s = (jax.nn.silu(y @ shared["wg"]) * (y @ shared["wi"])) @ shared["wo"]
    np.testing.assert_allclose(np.asarray(plain - none), np.asarray(s),
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gated - none),
        np.asarray(s * jax.nn.sigmoid(y @ gate)[:, None]), atol=1e-5)


# ---------------------------------------------------------------------------
# one layer: absorbed against expanded against the reference
# ---------------------------------------------------------------------------


def test_absorbed_and_expanded_forms_and_the_reference_agree_on_one_layer():
    """The same attention three ways on seeded weights: the program's
    expanded form (``mla_attention``), its absorbed form through the
    ``mla_decode`` kernel over a latent pool (one query, the last token),
    and the reference's blocked expanded form."""
    ref = _reference()
    model = _model()
    cfg, p = model.config, _params(model)
    T, bs = 50, 16
    mp = jax.tree.map(lambda a: a[1], p["layers"]["mla"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, T, 64))
    ln1 = p["layers"]["ln1"]["scale"][1]
    pos = jnp.arange(T)[None]
    with jax.default_matmul_precision("highest"):
        y = hybrid._rms(x, ln1, cfg.norm_eps)
        q_n, q_r, lat = hybrid.mla_project(cfg, mp, y, pos)
        expanded = hybrid.mla_attention(cfg, mp, q_n, q_r, lat)[0]   # [T,n,v]
        # the pool: pages 3, 0, 2, 1 hold the sequence in that order
        table = jnp.asarray([[3, 0, 2, 1]], jnp.int32)
        W = 256
        rows = jnp.pad(lat[0], ((0, 4 * bs - T), (0, W - lat.shape[-1])))
        pool = jnp.zeros((2, 6, bs, W), F32).at[1, table[0]].set(
            rows.reshape(4, bs, W))
        q = jnp.concatenate([hybrid.mla_absorb_q(cfg, mp, q_n[0, -1:]),
                             q_r[0, -1:]], -1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[-1])))
        o, fetched = mla_decode_attention(
            q, pool, table, jnp.asarray([T], jnp.int32),
            value_dim=cfg.kv_lora_rank, scale=cfg.mla_scale, layer=1)
        assert int(fetched.sum()) == -(-T // bs)    # the pages that hold T
        absorbed = hybrid.mla_absorb_o(cfg, mp, o)[0]               # [n, v]
    np.testing.assert_allclose(np.asarray(absorbed),
                               np.asarray(expanded[-1]), atol=2e-5)
    a = _arch(ref, cfg)
    w = _layer_weights(p, 1)
    want_lat = ref.latents(a, "float32", x[0], w, pos[0])
    np.testing.assert_allclose(np.asarray(lat[0]), np.asarray(want_lat),
                               atol=2e-5)
    rq_n, rq_r = ref.queries(a, "float32", y[0], w, pos[0])
    want = ref.attention(a, "float32", rq_n, rq_r, want_lat, w, pos[0])
    np.testing.assert_allclose(np.asarray(expanded), np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("pages", [1, 2, 4])
def test_kernel_counts_the_page_copies_it_starts(pages):
    """``mla_pages_read`` is read off the kernel's own walk: each grid step
    counts the copies it starts (its own later blocks and the next live
    sequence's first), and over the call they are the pages that hold the
    contexts: nothing past a context, nothing twice, a dead slot none."""
    bs, W = 16, 256
    ctx = jnp.asarray([50, 0, 16, 97, 1], jnp.int32)
    table = jnp.arange(5 * 7, dtype=jnp.int32).reshape(5, 7)
    pool = jax.random.normal(jax.random.PRNGKey(1), (2, 40, bs, W), F32)
    q = jax.random.normal(jax.random.PRNGKey(2), (5, 4, W), F32)
    o, fetched = mla_decode_attention(q, pool, table, ctx, value_dim=128,
                                      scale=0.1, layer=1,
                                      pages_per_compute_block=pages)
    assert fetched.shape == (5,)
    assert int(fetched.sum()) == int(jnp.sum(-(-ctx // bs)))
    assert not np.asarray(o[1]).any() and np.isfinite(np.asarray(o)).all()


def test_kernel_refuses_widths_that_do_not_fill_lane_tiles():
    with pytest.raises(ValueError, match="128-lane"):
        mla_decode_attention(jnp.zeros((1, 4, 144)), jnp.zeros((1, 4, 16, 144)),
                             jnp.zeros((1, 2), jnp.int32),
                             jnp.ones((1,), jnp.int32), value_dim=128,
                             scale=1.0, layer=0)


# ---------------------------------------------------------------------------
# the latent pool
# ---------------------------------------------------------------------------


def test_latent_pool_accounting_and_block_io():
    c = KVCacheConfig(num_layers=5, kv_heads=64, head_dim=192, block_size=64,
                      num_blocks=12, dtype=jnp.bfloat16, kind="latent",
                      latent_dim=576)
    assert c.payload_width == 640                       # whole lane tiles
    assert c.pool_shape == (5, 12, 64, 640)
    assert c.bytes_per_block == 5 * 64 * 640 * 2
    cache = BlockedKVCache(c)
    assert cache.data.shape == c.pool_shape and cache.scales is None
    assert cache.blocks_needed(65) == 2 and cache.blocks_needed(64) == 1
    blocks = cache.allocator.allocate(3)
    assert cache.free_blocks == 9
    rows = np.random.default_rng(0).normal(size=(5, 3, 64, 640)).astype(
        np.float32)
    cache.write_blocks(blocks, rows)
    back, scales = cache.read_blocks_host(blocks)
    assert scales is None
    np.testing.assert_array_equal(
        back, np.asarray(jnp.asarray(rows).astype(jnp.bfloat16)))
    cache.free(blocks)
    assert cache.free_blocks == 12 and cache.reclaim(2) == 0
    # a K/V pool is what it was
    kv = KVCacheConfig(num_layers=2, kv_heads=2, head_dim=8)
    assert kv.kind == "kv" and kv.pool_shape == (2, 256, 16, 2, 2, 8)


@pytest.mark.parametrize("bits", [8, 4, "fp8"])
def test_quantized_rungs_refuse_a_latent_pool_by_name(bits):
    with pytest.raises(LatentPoolUnsupported, match="latent pool"):
        KVCacheConfig(num_layers=1, kv_heads=1, head_dim=8, kind="latent",
                      latent_dim=144, quant_bits=bits)
    with pytest.raises(LatentPoolUnsupported):
        _engine(_model(), None, kv_quant_bits=bits)


def test_what_reads_a_page_by_its_heads_refuses_by_name():
    from deepspeed_tpu.serving import disagg

    model = _model()
    with pytest.raises(LatentPoolUnsupported, match="speculative"):
        _engine(model, None, spec_decode=True)
    eng = _engine(model, _params(model))
    eng.put([1], [np.arange(40, dtype=np.int32)], max_new_tokens=4)
    eng.serve_step()
    with pytest.raises(LatentPoolUnsupported, match="hand-off wire"):
        disagg.serialize_prefix(eng, np.arange(40, dtype=np.int32))
    with pytest.raises(LatentPoolUnsupported, match="migration"):
        eng.migrate_out_session(1)
    pools = eng.kv_cache.kv_state
    with pytest.raises(NotImplementedError, match="gather program"):
        hybrid_runner.ragged_forward(
            model.config, eng.params, pools, jnp.zeros((8,), jnp.int32),
            jnp.zeros((8,), jnp.int32), jnp.zeros((8,), jnp.int32),
            jnp.zeros((4, 12), jnp.int32), jnp.int32(8))
    eng.close()


# ---------------------------------------------------------------------------
# the engine against the full forward and the reference
# ---------------------------------------------------------------------------


def _greedy_by_apply(model, params, prompt, toks):
    """What the full forward picks greedily at every position that produced
    one of ``toks`` (one teacher-forced call: equal to ``toks`` exactly when
    every one of them is the full forward's own greedy pick)."""
    seq = jnp.asarray(list(prompt) + list(toks)[:-1], jnp.int32)
    lg = model.apply(params, seq[None])[0, len(prompt) - 1:]
    return [int(t) for t in jnp.argmax(lg, axis=-1)]


def test_engine_chunked_prefill_then_latent_decode_matches_apply():
    """Prompts of 70, 9 and 45 tokens through a 32-token step budget (the
    longest over three chunks of the prefill program, no gather program),
    then decode through the latent cache: single steps and bursts."""
    model = _model()
    p = _params(model)
    eng = _engine(model, p)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (70, 9, 45)]
    eng.put([1, 2, 3], prompts, max_new_tokens=10)
    out = eng.generate_all()
    for uid, prompt in zip((1, 2, 3), prompts):
        assert list(out[uid]) == _greedy_by_apply(model, p, prompt, out[uid]), uid
    s = eng.stats
    assert s["tokens_gather"] == 0 and s["prefill_chunks"] >= 5
    assert s["tokens_multi_decode"] > 0 and s["tokens_decode"] > 0
    # every decode step asked the kernel for whole contexts, two expert
    # layers' worth... of three latent layers
    assert s["mla_context_tokens"] > 0
    assert s["mla_pages_read"] * 16 >= s["mla_context_tokens"]
    assert s["mla_pages_read"] * 16 < s["mla_context_tokens"] + 16 * 3 * (
        s["tokens_decode"] + s["tokens_multi_decode"])
    assert s["moe_token_layers"] > 0 and s["state_slots"] == 0
    eng.close()


def test_engine_serving_tree_and_donation():
    model = _model()
    p = _params(model)
    eng = _engine(model, jax.tree.map(jnp.array, p), donate_params=True)
    assert set(eng.params) == {"embed", "final_norm", "unembed", "layers",
                               "experts", "mla", "dense"}
    # the expert layers alone: 3 layers, 1 of them the dense prologue
    assert eng.params["experts"]["wi"].shape[0] == 2
    assert eng.params["mla"]["wo"].shape[0] == 3
    assert eng.kv_cache.state_pool is None
    # (the programs hand their counters out and take none in)
    assert set(eng.kv_cache.kv_state) == {"kv"}
    eng.put([1], [np.arange(20, dtype=np.int32)], max_new_tokens=3)
    toks = list(eng.generate_all()[1])
    assert len(toks) == 3 and toks == _greedy_by_apply(
        model, p, np.arange(20), toks)
    eng.close()
    stacked = jax.tree.map(jnp.array, p)
    leaf = stacked["layers"]["moe"]["experts"]["wg"]
    hybrid.serving_params(model.config, stacked, donate=True)
    assert leaf.is_deleted()


def test_engine_logits_match_the_reference_full_forward():
    """Prefill through the chunk path and decode through the latent cache
    both agree with the reference's expanded full forward (logits, not
    tokens)."""
    ref = _reference()
    model = _model()
    p = _params(model)
    a = _arch(ref, model.config)
    prompt = np.random.default_rng(2).integers(0, 256, 75).astype(np.int32)
    eng = _engine(model, p, decode_steps=1)
    rows = []
    pick = eng._pick_greedy
    eng._pick_greedy = lambda lg, idx: (rows.append(
        np.asarray(eng._take_rows(lg, idx))[0]), pick(lg, idx))[1]
    eng.put([1], [prompt], max_new_tokens=6)
    toks = list(eng.generate_all()[1])
    eng.close()
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    top = {"embed_tokens": p["embed"]["tokens"], "norm":
           p["final_norm"]["scale"], "lm_head": p["unembed"]["kernel"],
           "dense_gate_proj": p["dense"]["wg"], "dense_up_proj":
           p["dense"]["wi"], "dense_down_proj": p["dense"]["wo"]}
    want = np.asarray(ref.forward_logits(
        a, [seq], [np.arange(len(prompt) - 1, len(seq))],
        lambda l: _layer_weights(p, l), top)[0])
    got = np.stack(rows[-len(toks):])
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < 2e-4, err


def test_prefix_cache_shares_latent_pages():
    """A second request whose first two pages are the first's takes them
    from the prefix cache (a latent page is a page) and answers as the full
    forward does."""
    model = _model()
    p = _params(model)
    eng = _engine(model, p, prefix_cache=True)
    rng = np.random.default_rng(4)
    shared = rng.integers(0, 256, 32).astype(np.int32)       # two pages
    first = np.concatenate([shared, rng.integers(0, 256, 9).astype(np.int32)])
    second = np.concatenate([shared, rng.integers(0, 256, 13).astype(np.int32)])
    eng.put([1], [first], max_new_tokens=4)
    eng.generate_all()
    eng.put([2], [second], max_new_tokens=6)
    out = eng.generate_all()
    assert eng.stats["prefix_hit_tokens"] == 32
    assert len(out[2]) == 6 and list(out[2]) == _greedy_by_apply(
        model, p, second, out[2])
    eng.close()


def test_host_tier_parks_and_restores_latent_pages():
    model = _model()
    p = _params(model)
    prompt = np.random.default_rng(6).integers(0, 256, 40).astype(np.int32)
    eng = _engine(model, p, host_kv_tier=True, host_tier_mb=8,
                  decode_steps=1)
    eng.put([1], [prompt], max_new_tokens=12)
    got = []
    while len(got) < 3:
        got += eng.serve_step().get(1, [])
    assert eng.page_out(1)
    while 1 in eng.state.seqs or eng._queue:
        got += eng.serve_step().get(1, [])
    assert eng.stats["paged_in"] == 1
    assert len(got) == 12 and got == _greedy_by_apply(model, p, prompt, got)
    eng.close()


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------


def test_the_shares_routed_parts_and_one_shared_expert_add_up_to_the_layer():
    """Every chip of the deployment computes its held experts' part of the
    routed sum plus the shared expert; summed over all the shares, with the
    shared expert counted once, that is the uncut layer: for the program's
    ``moe_ffn_share`` and for the reference's ``expert_block`` alike."""
    ref = _reference()
    model = _model(experts_held=None)               # all 16 experts
    cfg, p = model.config, _params(model)
    w = _layer_weights(p, 2)
    y = jax.random.normal(jax.random.PRNGKey(9), (24, 64))
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_block(_arch(ref, cfg, held=16), "float32", y, w)
        shared = ref.swiglu("float32", y, w["shared_gate_proj"],
                            w["shared_up_proj"], w["shared_down_proj"])
        by_ref = by_program = shared
        for offset in range(0, 16, 4):
            cut = {k: v[offset:offset + 4] if k.startswith("experts_") else v
                   for k, v in w.items()}
            by_ref = by_ref + ref.expert_block(
                _arch(ref, cfg, held=4, offset=offset), "float32", y,
                cut) - shared
            out, counts = moe_ffn_share(
                y, w["gate"], {"wg": cut["experts_gate_proj"],
                               "wi": cut["experts_up_proj"],
                               "wo": cut["experts_down_proj"]},
                cfg.gate, offset=offset, router_bias=w[
                    "e_score_correction_bias"],
                shared={"wg": w["shared_gate_proj"],
                        "wi": w["shared_up_proj"],
                        "wo": w["shared_down_proj"]})
            by_program = by_program + out - shared
    np.testing.assert_allclose(np.asarray(by_ref), np.asarray(whole),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(by_program), np.asarray(whole),
                               atol=2e-5)


def test_presets_count_their_parameters():
    cfg = get_model("kimi-k2", num_layers=5, experts_held=12,
                    vocab_size=20480).config
    mixer = (7168 * 1536 + 1536 + 1536 * 64 * 192 + 7168 * 576 + 512
             + 512 * 64 * 256 + 64 * 128 * 7168)
    expert = 3 * 7168 * 2048
    layer = mixer + 2 * 7168 + 7168 * 384 + 384 + 13 * expert
    assert cfg.num_params() == (5 * layer + 3 * 7168 * 18432
                                + 2 * 20480 * 7168 + 7168)
    assert math.isclose(mixer / 1e6, 101.1, abs_tol=0.05)
