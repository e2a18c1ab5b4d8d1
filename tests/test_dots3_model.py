"""The ``dots3_note`` family on the CPU at a toy size (``tiny-dots3``): both
latent mixers' absorbed form against their expanded form and the plain
reference; the selector against a table worked by hand; the engine (chunked
prefill, then decode through the latent pool, the selector's keys and the
windowed pool's rings) against the reference's full forward, past twice the
window and past ``index_topk`` tokens; the windowed pool's page bound and
``free``; what cannot hold a windowed pool refusing by name; the share
test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.ragged import (KVCacheConfig,
                                            SequenceDescriptor, StateManager,
                                            WindowedLatentPool,
                                            WindowedPoolUnsupported,
                                            WindowPoolConfig)
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops.pallas.paged_attention import mla_decode_attention
from deepspeed_tpu.parallel.moe import moe_ffn_share
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

F32 = jnp.float32


def _model(**kw):
    return get_model("tiny-dots3", param_dtype=F32, dtype=F32, **kw)


def _params(model, seed=0):
    """Seeded weights, the router's bias drawn so that it changes choices
    and the selector's norm bias so that dropping it would show."""
    p = model.init(jax.random.PRNGKey(seed))
    moe = p["layers"]["moe"]
    moe["router"] = moe["router"] * 4.0
    moe["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), moe["router_bias"].shape)
    mla = p["layers"]["mla"]
    mla["ik_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                             mla["ik_bias"].shape)
    return p


def _engine(model, params, **kw):
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    kw = dict(dict(kv_block_size=8, kv_blocks=64, max_tokens_per_step=16,
                   max_seqs_per_step=4, max_blocks_per_seq=16), **kw)
    return InferenceEngineV2(model, mesh=mesh, params=params, dtype=F32, **kw)


def _reference():
    from benchmarks.harness import manifest as mf

    return mf.load_module("references", "dots3_note")


def _arch(ref, cfg, held=None, offset=0):
    kinds = {"m": "full_attention", "w": "sliding_attention"}
    return ref.Arch.from_model(dict(
        hidden_size=cfg.hidden_size, intermediate_size=cfg.ffn_size,
        moe_intermediate_size=cfg.moe_ffn_size,
        num_attention_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        index_n_heads=cfg.index_n_heads, index_head_dim=cfg.index_head_dim,
        index_topk=cfg.index_topk,
        swa_num_attention_heads=cfg.window_num_heads,
        swa_q_lora_rank=cfg.window_q_lora_rank,
        swa_kv_lora_rank=cfg.window_kv_lora_rank,
        swa_qk_nope_head_dim=cfg.window_qk_nope_head_dim,
        swa_qk_rope_head_dim=cfg.window_qk_rope_head_dim,
        swa_v_head_dim=cfg.window_v_head_dim,
        swa_rope_theta=cfg.window_rope_theta,
        sliding_window_size=cfg.sliding_window,
        apply_mla_qkv_lora_rescale=cfg.mla_lora_rescale,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        first_k_dense_replace=cfg.first_k_dense,
        n_routed_experts=cfg.held if held is None else held,
        n_shared_experts=1, num_experts_per_tok=cfg.top_k,
        routed_scaling_factor=cfg.routed_scale, vocab_size=cfg.vocab_size,
        num_hidden_layers=cfg.num_layers, router_outputs=cfg.num_experts,
        expert_offset=offset, first_layer=cfg.first_layer,
        layer_types=[kinds[c] for c in cfg.layer_pattern],
        rope_scaling=None, scoring_func="sigmoid", norm_topk_prob=True,
        topk_method="noaux_tc", attention_gate_type="headwise",
        swa_attention_gate_type="headwise"))


_MIXER = {"wqa": "q_a_proj", "q_norm": "q_a_layernorm", "wqb": "q_b_proj",
          "wkva": "kv_a_proj_with_mqa", "kv_norm": "kv_a_layernorm",
          "wkvb": "kv_b_proj", "wo": "o_proj", "wgate": "gate_proj",
          "wiq": "indexer_wq_b", "wik": "indexer_wk",
          "ik_norm": "indexer_k_norm", "ik_bias": "indexer_k_norm_bias",
          "wiw": "indexer_weights_proj"}


def _layer_weights(params, l):
    """Layer ``l`` of the stacked tree under the reference's names."""
    lay = jax.tree.map(lambda a: a[l], params["layers"])
    moe = lay["moe"]
    mixers = {f"{pub}_{_MIXER[k]}": v for mixer, pub in (
        ("mla", "attn"), ("wmla", "swa")) for k, v in lay[mixer].items()}
    return {**mixers,
            "input_layernorm": lay["ln1"]["scale"],
            "post_attention_layernorm": lay["ln2"]["scale"],
            "gate": moe["router"],
            "e_score_correction_bias": moe["router_bias"],
            "experts_gate_proj": moe["experts"]["wg"],
            "experts_up_proj": moe["experts"]["wi"],
            "experts_down_proj": moe["experts"]["wo"],
            "shared_gate_proj": moe["shared"]["wg"],
            "shared_up_proj": moe["shared"]["wi"],
            "shared_down_proj": moe["shared"]["wo"]}


def _top(params):
    """The reference's top leaves of the stacked tree."""
    return {"embed_tokens": params["embed"]["tokens"],
            "norm": params["final_norm"]["scale"],
            "lm_head": params["unembed"]["kernel"],
            "dense_gate_proj": params["dense"]["wg"],
            "dense_up_proj": params["dense"]["wi"],
            "dense_down_proj": params["dense"]["wo"]}


def test_presets_lay_the_stack_out_as_published():
    c = _model().config
    assert c.mixer_kinds == (True, True, "w", "w", "w", True, "w", "w", "w")
    assert c.stack_plan == (2, ((True, 1), ("w", 3)))
    assert (c.kv_layers, c.window_layers, c.recurrent_layers) == (3, 6, 0)
    assert c.dense_layers == 1 and c.layer_kinds == (True,) * 9
    big = get_model("dots3-note", num_layers=9, experts_held=8,
                    vocab_size=19072).config
    assert big.stack_plan == c.stack_plan
    assert (big.latent_dim, big.index_key_dim, big.window_latent_dim) == (
        576, 128, 1088)
    full, win = big.mla_sizes(), big.mla_sizes(True)
    assert (full.heads, full.theta, win.heads, win.theta) == (
        128, 8e7, 64, 5e4)
    assert full.scale == pytest.approx(192 ** -0.5)
    assert win.scale == pytest.approx(256 ** -0.5)
    assert full.q_rescale == pytest.approx(5 ** 0.5)
    assert full.kv_rescale == pytest.approx(10 ** 0.5)
    assert win.q_rescale == win.kv_rescale == pytest.approx(5 ** 0.5)
    # the served tree: 3 full mixers, 6 sliding ones, 8 expert layers
    served = jax.eval_shape(
        lambda p: hybrid.serving_params(big, p),
        jax.eval_shape(get_model("dots3-note", num_layers=9, experts_held=8,
                                 vocab_size=19072).init,
                       jax.random.PRNGKey(0)))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(served))
    mixer = {k: sum(int(np.prod(x.shape[1:])) for x in jax.tree.leaves(
        served[k])) for k in ("mla", "wmla")}
    assert mixer["mla"] == 144049920                # 144.0 M
    assert round(mixer["wmla"] / 1e6, 1) == 90.8
    # 3,093 M and layer 0's router and shared-expert slots, which nothing
    # reads (24.9 M), at 19,072 vocabulary rows
    assert 3115e6 < count < 3122e6
    with pytest.raises(ValueError, match="of the full layers' kind"):
        get_model("tiny-dots3", window_attention_kind="gated")
    with pytest.raises(ValueError, match="latent"):
        get_model("tiny-trinity", index_topk=4)


@pytest.mark.parametrize("scores,visible,k,want", [
    # fewer than k visible: every visible one
    ([5., 1., 3., 9.], [1, 1, 1, 0], 4, [1, 1, 1, 0]),
    # exactly k visible
    ([5., 1., 3., 9.], [1, 1, 1, 0], 3, [1, 1, 1, 0]),
    # the k largest of more, negative and zero scores among them
    ([5., -1., 0., 9., 3.], [1, 1, 1, 1, 1], 3, [1, 0, 0, 1, 1]),
    # a tie at the k-th score goes to the earlier token
    ([2., 7., 2., 2., 7.], [1, 1, 1, 1, 1], 3, [1, 1, 0, 0, 1]),
    # -0.0 and 0.0 are one score: still the earlier
    ([-0., 0., 4., 0.], [1, 1, 1, 1], 2, [1, 0, 1, 0]),
    # a tie among invisible ones takes none of them
    ([1., 1., 1., 1.], [1, 0, 1, 0], 3, [1, 0, 1, 0]),
])
def test_selector_against_a_hand_table(scores, visible, k, want):
    got = hybrid.topk_mask(jnp.asarray([scores], F32),
                           jnp.asarray([visible], bool), k)
    assert [int(x) for x in got[0]] == want
    # the reference's rule (top_k over the visible prefix) agrees where the
    # visible ones are a causal prefix
    # (``lax.top_k`` orders -0.0 below 0.0: the reference's scores are sums
    # of ReLUs, and the program's pass through ``+ 0.0``)
    if visible == sorted(visible, reverse=True) and "-0.0" not in map(
            str, scores):
        ref = _reference()
        a = _arch(ref, _model(index_topk=k).config)
        sel = ref.select(a, jnp.asarray([scores], F32),
                         jnp.asarray([sum(visible) - 1]))
        assert [int(x) for x in sel[0]] == want


def test_index_scores_by_hand():
    """``I = sum_h w_h relu(q_h . k)``: a negative product adds nothing."""
    q = jnp.asarray([[[1., 0.], [0., 2.]]])                # [T=1, ni=2, di=2]
    w = jnp.asarray([[0.5, 3.0]])
    keys = jnp.asarray([[2., 1.], [-4., 1.], [1., -1.]])
    got = hybrid.index_scores(q, w, keys)
    np.testing.assert_allclose(np.asarray(got), [[0.5 * 2 + 3 * 2, 3 * 2,
                                                  0.5 * 1]])


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "sliding"])
def test_absorbed_and_expanded_forms_and_the_reference_agree(windowed):
    """One layer's attention three ways on seeded weights: the program's
    expanded form (under the selector's mask or the band), its absorbed form
    through the ``mla_decode`` kernel (one query, the last token: over its
    pages with the choice in the mask, or over a ring of pages with a lower
    bound), and
    the reference's blocked expanded form."""
    ref = _reference()
    model = _model()
    cfg, p = model.config, _params(model)
    T, bs = 50, 8
    l = 3 if windowed else 1
    name = "wmla" if windowed else "mla"
    mp = jax.tree.map(lambda a: a[l], p["layers"][name])
    z = cfg.mla_sizes(windowed)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, T, 64))
    pos = jnp.arange(T)[None]
    with jax.default_matmul_precision("highest"):
        y = hybrid._rms(x, p["layers"]["ln1"]["scale"][l], cfg.norm_eps)
        q_n, q_r, lat, cq0 = hybrid.mla_project(cfg, mp, y, pos, windowed,
                                                query_latent=True)
        sel = None if windowed else hybrid.select_tokens(cfg, mp, y, cq0, pos)
        expanded = hybrid.mla_attention(cfg, mp, q_n, q_r, lat, windowed,
                                        sel)[0]                  # [T, n, v]
        W = 256
        q = jnp.concatenate([hybrid.mla_absorb_q(cfg, mp, q_n[0, -1:]),
                             q_r[0, -1:]], -1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[-1])))
        rows = jnp.pad(lat[0], ((0, 0), (0, W - lat.shape[-1])))
        if windowed:
            # a ring of 4 pages: position p in entry (p // bs) % 4
            ring = jnp.asarray([2, 0, 3, 1], jnp.int32)
            pool = jnp.zeros((7, 5, bs, W), F32)
            for t in range(T - 4 * bs + bs, T):     # what the ring still has
                pool = pool.at[l, ring[(t // bs) % 4], t % bs].set(rows[t])
            lo = T - cfg.sliding_window
            first = lo // bs
            table = ring[(first + jnp.arange(4)) % 4][None]
            o, _ = mla_decode_attention(
                q, pool, table, jnp.asarray([T - first * bs], jnp.int32),
                value_dim=z.kv_rank, scale=z.scale, layer=l,
                lower=jnp.asarray([lo - first * bs], jnp.int32))
        else:
            # the sequence's own pages 3, 0, 6, ... and the selector's choice
            # as one more term of the kernel's mask
            assert int(sel[0, -1].sum()) == cfg.index_topk
            pages = -(-T // bs)
            table = jnp.asarray([[3, 0, 6, 5, 1, 4, 2]], jnp.int32)
            pool = jnp.zeros((2, 8, bs, W), F32).at[1, table[0]].set(
                jnp.pad(rows, ((0, pages * bs - T), (0, 0))).reshape(
                    pages, bs, W))
            o, _ = mla_decode_attention(
                q, pool, table, jnp.asarray([T], jnp.int32),
                value_dim=z.kv_rank, scale=z.scale, layer=1,
                pages_per_compute_block=2,
                chosen=jnp.pad(sel[0, -1:], ((0, 0), (0, pages * bs - T))))
        absorbed = hybrid.mla_absorb_o(cfg, mp, o, windowed)[0]     # [n, v]
    np.testing.assert_allclose(np.asarray(absorbed),
                               np.asarray(expanded[-1]), atol=2e-5)
    a = _arch(ref, cfg)
    w = _layer_weights(p, l)
    w.update(ref.mixer_weights(a, l, w))
    want_lat, keys = ref.leaves_behind(a, "float32", not windowed, x[0], w,
                                       pos[0])
    np.testing.assert_allclose(np.asarray(lat[0]), np.asarray(want_lat),
                               atol=2e-5)
    rq_n, rq_r, c_q0 = ref.queries(a, "float32", not windowed, y[0], w,
                                   pos[0])
    back = pos[0][:, None] - pos[0][None, :]
    if windowed:
        visible = (back >= 0) & (back < cfg.sliding_window)
    else:
        visible = ref.select(a, ref.selector_scores(
            a, "float32", y[0], c_q0, keys, w, pos[0]), pos[0])
        assert bool((visible == sel[0]).all())
        assert int(visible[-1].sum()) == cfg.index_topk < T
    want = ref.attention(a, "float32", not windowed, rq_n, rq_r, want_lat, w,
                         visible)
    np.testing.assert_allclose(np.asarray(expanded), np.asarray(want),
                               atol=2e-5)


def test_engine_logits_match_the_reference_full_forward():
    """Prefill in chunks of 16 (a window of 13 and a ring of 3 pages of 8:
    every chunk writes over its own rows), then decode, single steps and
    bursts, through the latent pool, the selector's keys and the rings,
    against the reference's full forward (logits, not tokens): prompts past
    twice the window and past ``index_topk`` (16) tokens, and a short one
    that never fills its ring."""
    ref = _reference()
    model = _model()
    cfg, p = model.config, _params(model)
    a = _arch(ref, cfg)
    rng = np.random.default_rng(2)
    prompts = {1: rng.integers(0, 256, 45).astype(np.int32),
               2: rng.integers(0, 256, 7).astype(np.int32)}
    eng = _engine(model, p, decode_steps=1)
    assert eng.kv_cache.store("wkv").config.ring_pages == 4
    rows, slots = [], []
    pick, schedule = eng._pick_greedy, eng.scheduler.schedule

    def tap(lg, idx):
        rows.append(np.asarray(eng._take_rows(lg, idx)))
        return pick(lg, idx)

    def scheduled():
        out = schedule()
        slots.append([seq.uid for seq, _, _ in out])
        return out

    eng._pick_greedy, eng.scheduler.schedule = tap, scheduled
    eng.put(list(prompts), list(prompts.values()), max_new_tokens=12)
    got = {uid: [] for uid in prompts}
    toks = {uid: [] for uid in prompts}
    while eng.state.seqs or eng._queue:
        seen = len(rows)
        out = eng.serve_step()
        if eng.decode_steps == 1 and all(len(t) >= 6 for t in toks.values()):
            eng.decode_steps = 4                    # the rest in bursts
        for uid, new in out.items():
            new = [new] if isinstance(new, int) else list(new)
            if len(rows) > seen and len(new) == 1 and uid in slots[-1]:
                got[uid].append((len(toks[uid]),
                                 rows[-1][slots[-1].index(uid)]))
            toks[uid].extend(new)
    stats = dict(eng.stats)
    assert eng.kv_cache.store("wkv").pages_in_use == 0     # all given back
    assert eng.kv_cache.free_blocks == 63
    eng.close()
    assert all(len(t) == 12 for t in toks.values())
    seqs = {uid: np.concatenate([prompts[uid],
                                 np.asarray(toks[uid][:-1], np.int32)])
            for uid in prompts}
    blocks = ref.QUERY_BLOCK, ref.KEY_BLOCK
    ref.QUERY_BLOCK, ref.KEY_BLOCK = 8, 16          # several of each
    try:
        want = ref.forward_logits(
            a, [np.pad(s, (0, 64 - len(s))) for s in seqs.values()],
            [np.arange(len(prompts[u]) - 1, len(s)) for u, s in seqs.items()],
            lambda l: _layer_weights(p, l), _top(p))
    finally:
        ref.QUERY_BLOCK, ref.KEY_BLOCK = blocks
    for (uid, rows_of), w in zip(got.items(), want):
        w = np.asarray(w)
        assert len(rows_of) >= 6
        # every token, tapped or from a burst, is the reference's pick
        assert [int(r.argmax()) for r in w] == toks[uid]
        for j, row in rows_of:
            err = np.linalg.norm(row - w[j]) / np.linalg.norm(w[j])
            assert err < 2e-4, (uid, j, err)
    # the selector kept 16 of the long prompt's tokens, all of the short's
    assert 0 < stats["dsa_rows_selected"] < stats["dsa_rows_visible"]
    assert stats["window_rows_read"] > 0
    assert stats["window_pages_recycled"] > 0
    assert stats["mla_context_tokens"] == 0         # the dense latent path's


def test_reference_refuses_what_it_does_not_write_down():
    ref = _reference()
    cfg = _model().config
    a = _arch(ref, cfg)
    assert [a.is_full(l) for l in range(9)] == [
        True, True, False, False, False, True, False, False, False]
    assert a.dense_layers == 1 and a.full_layers == 3
    table = a.leaf_table()
    assert all(leaf.per_layer != leaf.path.startswith((
        "dense.", "embed.", "final_norm.", "unembed.")) for leaf in table)
    w = {leaf.published: leaf.path for leaf in table}
    assert ref.mixer_weights(a, 1, w)["q_a_proj"] == "mla.wqa"
    assert "indexer_wk" in ref.mixer_weights(a, 1, w)
    assert ref.mixer_weights(a, 2, w)["q_a_proj"] == "wmla.wqa"
    assert "indexer_wk" not in ref.mixer_weights(a, 2, w)
    with pytest.raises(NotImplementedError, match="no loss_and_grads"):
        ref.loss_and_grads()
    assert ref.train_flops_per_token(a, 64) > 0


def test_the_shares_routed_parts_and_one_shared_expert_add_up_to_the_layer():
    """Every chip of the deployment computes its held experts' part of the
    routed sum plus the shared expert; summed over all the shares (four of
    four experts here; 32 of 8 in the cell), with the shared expert counted
    once, that is the uncut layer: for the program's ``moe_ffn_share`` and
    for the reference's ``expert_block`` alike."""
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
                _arch(ref, cfg, held=4, offset=offset), "float32", y, cut,
                shared=False)
            out, _ = moe_ffn_share(
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


@pytest.mark.parametrize("window,block,ring", [(513, 64, 10), (13, 8, 4),
                                               (64, 64, 3), (1, 16, 2)])
def test_windowed_pool_page_bound_and_free(window, block, ring):
    """A sequence's pages in a sliding layer never exceed ``ceil((window +
    block - 1) / block) + 1`` whatever its length; they are taken one at a
    time as it grows, and ``free`` returns them all."""
    cfg = WindowPoolConfig(layers=2, window=window, row_dim=1088,
                           block_size=block, num_blocks=2 * ring + 1)
    assert cfg.ring_pages == ring
    assert cfg.pool_shape == (2, 2 * ring + 1, block, 1152)
    pool = WindowedLatentPool(cfg)
    assert pool.scratch_block == 2 * ring and pool.free_blocks == 2 * ring
    one, two, late = (SequenceDescriptor(uid, np.empty(0, np.int32))
                      for uid in range(3))
    for seq in (one, two, late):
        pool.take(seq)
    for tokens in (1, block, block + 1, 3 * block, 50 * block, 24000):
        assert pool.grow(one, tokens)
        blocks = one.held["wkv"]
        assert len(blocks) == min(-(-tokens // block), ring) <= ring
        assert pool.pages_in_use == len(blocks)
    assert pool.grow(two, 10 ** 6)
    other = two.held["wkv"]
    assert len(other) == ring and pool.free_blocks == 0
    assert not set(other) & set(blocks)
    assert not pool.grow(late, 1) and not len(late.held["wkv"])  # none left
    pool.free(blocks)
    pool.free(other)
    assert pool.free_blocks == 2 * ring and pool.pages_in_use == 0
    with pytest.raises(ValueError, match="double free"):
        pool.free(np.asarray([other[0], other[0]]))


def test_state_manager_grows_a_ring_and_gives_it_back():
    from deepspeed_tpu.inference.ragged import BlockedKVCache

    kv = BlockedKVCache(KVCacheConfig(
        num_layers=3, kv_heads=4, head_dim=48, block_size=8, num_blocks=32,
        dtype=F32, kind="latent", latent_dim=144, index_key_dim=32),
        stores=[WindowedLatentPool(WindowPoolConfig(
            layers=6, window=13, row_dim=144, block_size=8, num_blocks=6,
            dtype=F32))])
    ring = kv.store("wkv")
    assert set(kv.kv_state) == {"kv", "ik", "wkv"}
    assert kv.kv_state["ik"].shape == (3, 32, 8, 32)
    assert kv.config.bytes_per_block == 3 * 8 * (256 + 32) * 4
    state = StateManager(kv, max_blocks_per_seq=16)
    seq = state.get_or_create(1, np.arange(100, dtype=np.int32))
    assert state.ensure_capacity(seq, 20)
    assert (len(seq.kv_blocks), len(seq.held["wkv"])) == (3, 3)
    assert state.ensure_capacity(seq, 100)
    assert (len(seq.kv_blocks), len(seq.held["wkv"])) == (13, 4)
    other = state.get_or_create(2, np.arange(9, dtype=np.int32))
    assert state.ensure_capacity(other, 8)
    assert not state.ensure_capacity(other, 9)     # the windowed pool is out
    state.release(1)
    assert ring.pages_in_use == 1 and kv.free_blocks == 31
    assert state.ensure_capacity(other, 9)
    state.release(2)
    assert ring.pages_in_use == 0 and kv.free_blocks == 32
    with pytest.raises(ValueError, match="beside a latent pool"):
        KVCacheConfig(num_layers=1, kv_heads=1, head_dim=8, index_key_dim=8)


def test_what_cannot_hold_a_windowed_pool_refuses_by_name():
    from deepspeed_tpu.inference.ragged import LatentPoolUnsupported
    from deepspeed_tpu.inference.ragged.prefix_cache import PrefixCache
    from deepspeed_tpu.serving import disagg

    model = _model()
    with pytest.raises(WindowedPoolUnsupported, match="host KV tier"):
        _engine(model, None, host_kv_tier=True)
    with pytest.raises(LatentPoolUnsupported, match="speculative"):
        _engine(model, None, spec_decode=True)
    eng = _engine(model, _params(model))            # prefix_cache defaults on
    assert eng.kv_cache.prefix_cache is None        # ... and is switched off
    eng.put([1], [np.arange(40, dtype=np.int32)], max_new_tokens=4)
    eng.serve_step()
    with pytest.raises(WindowedPoolUnsupported, match="migration"):
        eng.migrate_out_session(1)
    with pytest.raises(WindowedPoolUnsupported, match="hand-off wire"):
        disagg.serialize_prefix(eng, np.arange(40, dtype=np.int32))
    # a prefix cache attached behind the engine's back: the sequences'
    # manager refuses the hit
    eng.kv_cache.prefix_cache = PrefixCache(8)
    seq = eng.state.get_or_create(2, np.arange(40, dtype=np.int32))
    with pytest.raises(WindowedPoolUnsupported, match="prefix cache"):
        eng.state.attach_prefix(seq)
    eng.close()
