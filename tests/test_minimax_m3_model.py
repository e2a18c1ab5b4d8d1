"""The ``minimax_m3`` family on the CPU at a toy size (``tiny-m3``: blocks of
8, the 2 best, 2 KV groups, one dense and two expert layers): the learned
block selector against a table worked by hand and against the plain
reference; the engine (prefill in chunks that end inside blocks, then decode
through the K/V pool and the pooled keys) against the reference's full
forward; the pooled keys' running maximum against the maximum from scratch
after chunks, token steps, preemption and recompute; ``swigluoai`` with both
clamps at work; what a pool with pooled keys refuses by name; the share
test."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.ragged import (KVCacheConfig,
                                            LatentPoolUnsupported,
                                            PooledKeysUnsupported)
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops import block_sparse
from deepspeed_tpu.parallel.moe import Glu, moe_ffn_share
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

F32 = jnp.float32


def _model(**kw):
    return get_model("tiny-m3", param_dtype=F32, dtype=F32, **kw)


def _params(model, seed=0):
    """Seeded weights, the router's bias drawn so that it changes choices,
    the feed-forwards' inputs large enough that ``swigluoai`` clamps."""
    p = model.init(jax.random.PRNGKey(seed))
    moe = p["layers"]["moe"]
    moe["router"] = moe["router"] * 4.0
    moe["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), moe["router_bias"].shape)
    for ffn in (p["dense"], moe["shared"], moe["experts"]):
        ffn["wg"], ffn["wi"] = ffn["wg"] * 4.0, ffn["wi"] * 4.0
        ffn["wo"] = ffn["wo"] / 16.0
    return p


def _engine(model, params, **kw):
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    kw = dict(dict(kv_block_size=8, kv_blocks=64, max_tokens_per_step=20,
                   max_seqs_per_step=4, max_blocks_per_seq=16), **kw)
    return InferenceEngineV2(model, mesh=mesh, params=params, dtype=F32, **kw)


def _reference():
    from benchmarks.harness import manifest as mf

    return mf.load_module("references", "minimax_m3")


def _arch(ref, cfg, held=None, offset=0):
    sz = cfg.msa
    return ref.Arch.from_model(dict(
        hidden_size=cfg.hidden_size, intermediate_size=cfg.moe_ffn_size,
        dense_intermediate_size=cfg.ffn_size,
        shared_intermediate_size=cfg.shared_ffn_size,
        num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.kv_heads,
        head_dim=cfg.head_dim,
        rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor),
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        num_local_experts=cfg.held if held is None else held,
        num_experts_per_tok=cfg.top_k, n_shared_experts=1,
        routed_scaling_factor=cfg.routed_scale,
        swiglu_alpha=cfg.swiglu_alpha, swiglu_limit=cfg.swiglu_limit,
        vocab_size=cfg.vocab_size, num_hidden_layers=cfg.num_layers,
        router_outputs=cfg.num_experts, expert_offset=offset,
        first_layer=cfg.first_layer,
        moe_layer_freq=[int(l >= cfg.first_k_dense)
                        for l in range(len(cfg.layer_pattern))],
        msa_block_size=sz.block, msa_topk=sz.topk,
        msa_index_heads=sz.index_heads, msa_index_dim=sz.index_dim,
        msa_local_blocks=sz.local_blocks, msa_init_blocks=sz.init_blocks,
        scoring_func="sigmoid", use_routing_bias=True, hidden_act="swigluoai",
        use_qk_norm=True, qk_norm_type="per_head",
        attention_output_gate=False, tie_word_embeddings=False))


_ATTN = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
         "q_norm": "q_norm", "k_norm": "k_norm", "wsq": "indexer_q_proj",
         "wsk": "indexer_k_proj"}


def _layer_weights(params, l):
    """Layer ``l`` of the stacked tree under the reference's names."""
    lay = jax.tree.map(lambda a: a[l], params["layers"])
    moe = lay["moe"]
    return {**{_ATTN[k]: v for k, v in lay["attn"].items()},
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
    return {"embed_tokens": params["embed"]["tokens"],
            "norm": params["final_norm"]["scale"],
            "lm_head": params["unembed"]["kernel"],
            "dense_gate_proj": params["dense"]["wg"],
            "dense_up_proj": params["dense"]["wi"],
            "dense_down_proj": params["dense"]["wo"]}


def _streams(ref, a, params, ids):
    """The reference's input to every layer for the sequence ``ids`` (padded
    with zeros to whole blocks: attention is causal), and its output."""
    pad = (-len(ids)) % a.msa_block_size
    x = params["embed"]["tokens"][jnp.asarray(np.pad(ids, (0, pad)))]
    xs = []
    for l in range(a.num_hidden_layers):
        xs.append(x)
        x = ref.layer(a, "float32", l, x, _layer_weights(params, l),
                      _top(params))
    return xs, x


def test_presets_lay_the_stack_out_as_published():
    c = _model().config
    assert c.mixer_kinds == (True,) * 3 and c.dense_layers == 1
    assert c.stack_plan == (2, ((True, 1),))
    assert (c.kv_layers, c.window_layers, c.recurrent_layers) == (3, 0, 0)
    assert c.msa == block_sparse.MsaSizes(block=8, topk=2, index_heads=2,
                                          index_dim=16, local_blocks=2)
    big = get_model("minimax-m3", num_layers=5, first_layer=2,
                    experts_held=8, vocab_size=25088).config
    assert big.dense_layers == 1 and big.stack_plan == (4, ((True, 1),))
    assert big.msa.width == 19 and big.glu == Glu("swigluoai", 1.702, 7.0)
    shapes = hybrid._shapes(big)["layers"]["attn"]
    assert shapes["wq"] == (5, 6144, 64, 128)            # no output gate
    assert shapes["q_norm"] == (5, 64, 128) and shapes["k_norm"] == (5, 4, 128)
    assert shapes["wsq"] == (5, 6144, 4, 4, 128)
    assert shapes["wsk"] == (5, 6144, 4, 128)
    # the count of the configuration's ``sizing``: five layers of mixer,
    # indexer and norms, and of router, bias, shared and 8 routed experts
    # (the dense layer's slot too, in the stacked tree), the dense
    # feed-forward, embedding, head and final norm
    expert = 3 * 6144 * 3072
    assert big.num_params() == 5 * (
        106_963_456 + 15_728_640 + 12_288
        + 786_432 + 128 + 9 * expert) + 3 * 6144 * 12288 \
        + 2 * 25088 * 6144 + 6144 == 3_700_274_304
    with pytest.raises(ValueError, match="msa_topk"):
        _model(sparse_topk=5, sparse_block_size=8, sparse_kernel_size=8,
               sparse_kernel_stride=4, sparse_window_size=8)


@pytest.mark.parametrize("t,want", [
    # one KV head, one indexer head of one dim: a block's score is its
    # pooled key. Blocks of 4, the 2 best, the first and the local two.
    (3, [0]),                       # its own block alone
    (9, [0, 1, 2]),                 # first, previous, own
    (19, [0, 1, 2, 3, 4]),          # 5 blocks: everything is read
    (23, [0, 2, 3, 4, 5]),          # of 1..3 the best two: 2 (9.) and 3 (7.)
    (27, [0, 2, 3, 5, 6]),          # of 1..4: 2 and 3 still (4 scores 7. too:
                                    # the tie goes to the lower index)
    (31, [0, 2, 5, 6, 7]),          # of 1..5: 2 (9.) and 5 (8.)
])
def test_selector_against_a_hand_table(t, want):
    sz = block_sparse.MsaSizes(block=4, topk=2, index_heads=1, index_dim=1)
    pooled = jnp.asarray([5., 1., 9., 7., 7., 8., 99., 99.])[:, None, None]
    mask, visible = block_sparse.msa_select(
        sz, jnp.ones((1, 1, 1, 1)), pooled, jnp.asarray([t]))
    assert list(np.flatnonzero(np.asarray(mask[0, 0]))) == want
    assert int(visible[0]) == t // 4 + 1


def test_selector_sums_the_heads_scores_and_scales_them():
    sz = block_sparse.MsaSizes(block=2, topk=1, index_heads=2, index_dim=4,
                               local_blocks=1)
    rng = np.random.default_rng(0)
    qi = jnp.asarray(rng.normal(size=(3, 2, 2, 4)), F32)
    pooled = jnp.asarray(rng.normal(size=(6, 2, 4)), F32)
    t = jnp.asarray([11, 7, 2])
    mask, _ = block_sparse.msa_select(sz, qi, pooled, t)
    score = np.einsum("qkhd,bkd->qkb", qi, pooled) / 2.0
    for q in range(3):
        own = int(t[q]) // 2
        for k in range(2):
            want = {0, own}
            if own >= 2:
                want.add(1 + int(np.argmax(score[q, k, 1:own])))
            assert set(np.flatnonzero(np.asarray(mask[q, k]))) == want


def test_swigluoai_clamps_the_gate_from_above_and_the_up_projection_twice():
    glu = Glu("swigluoai", 1.702, 7.0)
    gate = jnp.asarray([-9.0, -1.0, 0.5, 7.0, 12.0])
    up = jnp.asarray([-30.0, -7.0, 0.25, 6.0, 30.0])
    g = np.minimum(np.asarray(gate), 7.0)
    u = np.clip(np.asarray(up), -7.0, 7.0)
    want = (u + 1.0) * g / (1.0 + np.exp(-1.702 * g))
    np.testing.assert_allclose(np.asarray(glu(gate, up)), want, rtol=1e-6)
    # the gate is not clamped from below, the clamps are not the identity
    assert float(glu(gate, up)[0]) != float(glu(jnp.maximum(gate, -7.0), up)[0])
    assert float(glu(gate, up)[4]) == float(glu(jnp.asarray(7.0),
                                                jnp.asarray(7.0)))
    ref = _reference()
    a = _arch(ref, _model().config)
    np.testing.assert_allclose(np.asarray(ref.swigluoai(a, gate, up)), want,
                               rtol=1e-6)
    assert Glu()(gate, up).tolist() == (jax.nn.silu(gate) * up).tolist()
    with pytest.raises(ValueError, match="glu kind"):
        Glu("geglu")


def test_full_forward_selects_the_references_blocks_and_gives_its_logits():
    """The program's forward without a cache (the mask on the dense form)
    against the reference, layer by layer: the same blocks for every query
    and KV head, then the same logits; both clamps of ``swigluoai`` at work
    in the dense, the routed and the shared feed-forward."""
    ref = _reference()
    model = _model()
    cfg, p = model.config, _params(model)
    a = _arch(ref, cfg)
    ids = np.random.default_rng(3).integers(0, 256, 96).astype(np.int32)
    xs, out = _streams(ref, a, p, ids)
    pos = jnp.arange(96)
    sp = hybrid.serving_params(cfg, p)
    picked = 0
    for l, x in enumerate(xs):
        w = _layer_weights(p, l)
        y = ref.rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
        _, _, ki = ref.leaves_behind(a, "float32", x, w, pos)
        want = ref.chosen_blocks(a, ref.block_scores(
            a, "float32", y, ref.pooled_keys(a, ki), w), pos)
        ap = jax.tree.map(lambda t: t[l], sp["attn"])
        got = hybrid.msa_mask(cfg, ap, y[None], pos[None])[0]
        assert bool((got == want).all())
        picked += int(want[-1].sum())
        # the last query sees 12 blocks and reads 5 a KV head
        assert int(want[-1].sum()) == 2 * cfg.msa.width == 10
        if l:       # an expert layer: both clamps bite in its feed-forward
            y2 = ref.rms_norm(xs[l], w["post_attention_layernorm"], 1e-6)
            pre = np.asarray(y2 @ w["shared_gate_proj"])
            assert pre.max() > 7.0 and np.asarray(
                y2 @ w["shared_up_proj"]).min() < -7.0
    want = ref.head_logits(a, "float32", out, p["final_norm"]["scale"],
                           p["unembed"]["kernel"])
    got = model.apply(p, jnp.asarray(ids)[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.fixture(scope="module")
def served():
    """Two prompts through the engine: chunks of at most 20 tokens (they end
    inside blocks of 8), single token steps, then bursts; the rows the engine
    sampled from, the counters after the prompts and at the end, and the
    chunks as they were scheduled, ``(uid, first position, tokens)``."""
    model = _model()
    p = _params(model)
    rng = np.random.default_rng(2)
    prompts = {1: rng.integers(0, 256, 77).astype(np.int32),
               2: rng.integers(0, 256, 13).astype(np.int32)}
    eng = _engine(model, p, decode_steps=1)
    rows, slots, chunks = [], [], []
    pick, schedule = eng._pick_greedy, eng.scheduler.schedule

    def tap(lg, idx):
        rows.append(np.asarray(eng._take_rows(lg, idx)))
        return pick(lg, idx)

    def scheduled():
        out = schedule()
        slots.append([seq.uid for seq, _, _ in out])
        chunks.extend((seq.uid, int(start), len(new))
                      for seq, new, start in out if len(new) > 1)
        return out

    eng._pick_greedy, eng.scheduler.schedule = tap, scheduled
    eng.put(list(prompts), list(prompts.values()), max_new_tokens=12)
    got = {uid: [] for uid in prompts}
    toks = {uid: [] for uid in prompts}
    after_prompts = None
    while eng.state.seqs or eng._queue:
        seen = len(rows)
        out = eng.serve_step()
        if after_prompts is None and all(toks.values()):
            after_prompts = dict(eng.stats)
        if eng.decode_steps == 1 and all(len(t) >= 6 for t in toks.values()):
            eng.decode_steps = 4                    # the rest in bursts
        for uid, new in out.items():
            new = [new] if isinstance(new, int) else list(new)
            if len(rows) > seen and len(new) == 1 and uid in slots[-1]:
                got[uid].append((len(toks[uid]),
                                 rows[-1][slots[-1].index(uid)]))
            toks[uid].extend(new)
    stats = dict(eng.stats)
    free = eng.kv_cache.available_blocks    # (whole pages stay cached)
    eng.close()
    return model, p, prompts, toks, got, after_prompts, stats, free, chunks


def test_engine_logits_match_the_reference_full_forward(served):
    model, p, prompts, toks, got, _, stats, free, _ = served
    ref = _reference()
    a = _arch(ref, model.config)
    assert free == 63 and all(len(t) == 12 for t in toks.values())
    seqs = {uid: np.concatenate([prompts[uid],
                                 np.asarray(toks[uid][:-1], np.int32)])
            for uid in prompts}
    blocks = ref.QUERY_BLOCK, ref.KEY_BLOCK
    ref.QUERY_BLOCK, ref.KEY_BLOCK = 8, 16          # several of each
    try:
        want = ref.forward_logits(
            a, list(seqs.values()),
            [np.arange(len(prompts[u]) - 1, len(seqs[u])) for u in seqs],
            lambda l: _layer_weights(p, l), _top(p))
    finally:
        ref.QUERY_BLOCK, ref.KEY_BLOCK = blocks
    for uid, w in zip(seqs, want):
        assert len(got[uid]) >= 6
        for j, row in got[uid]:
            np.testing.assert_allclose(row, np.asarray(w[j]), atol=3e-5)
        # the bursts hand out ids alone
        assert [int(r.argmax()) for r in np.asarray(w)] == toks[uid]
    assert stats["calls_gather"] == 0 < stats["calls_multi_decode"]
    assert stats["calls_prefill"] == stats["prefill_chunk_calls"] >= 5


def test_engine_counts_the_references_choice_in_chunk_and_token_steps(served):
    """What the step programs counted over the prompts' chunks and the token
    steps after them (blocks chosen and visible, queries that read
    everything) is what the reference chooses for the same queries: every
    position of both sequences but the last token's, in every layer."""
    model, p, prompts, toks, _, after_prompts, stats, _, _ = served
    ref = _reference()
    cfg = model.config
    a = _arch(ref, cfg)
    want = np.zeros(3, int)
    for uid in prompts:
        ids = np.concatenate([prompts[uid], np.asarray(toks[uid][:-1])])
        n = len(ids) + (-len(ids)) % 8
        pos = jnp.arange(n)
        xs, _ = _streams(ref, a, p, ids)
        t = np.arange(len(ids))
        for l, x in enumerate(xs):
            w = _layer_weights(p, l)
            y = ref.rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
            _, _, ki = ref.leaves_behind(a, "float32", x, w, pos)
            chosen = np.asarray(ref.chosen_blocks(a, ref.block_scores(
                a, "float32", y, ref.pooled_keys(a, ki), w), pos))
            want += [chosen[:len(ids)].sum(),
                     (t // 8 + 1).sum() * cfg.kv_heads,
                     (t // 8 + 1 <= cfg.msa.width).sum()]
    names = ("msa_blocks_chosen", "msa_blocks_visible", "msa_dense_queries")
    assert [stats[k] for k in names] == list(want)
    # both kinds of step chose: the prompts' chunks, then the token steps
    assert 0 < after_prompts["msa_blocks_chosen"] < stats["msa_blocks_chosen"]
    assert stats["msa_blocks_chosen"] < stats["msa_blocks_visible"]


def test_engine_counts_the_blocks_a_tile_visits_as_the_reference_folds_them(
        served):
    """The chunk kernel's two counters over the prompts' chunks are the
    reference's choice folded by tile: a tile is ``chunk_tile`` queries of a
    chunk from its first position on (8 here, a block; chunks start and end
    inside blocks, so tiles straddle them), it visits the union of what its
    real queries chose, a KV head, and sees what its last real query sees.
    Token steps count neither."""
    from deepspeed_tpu.ops.pallas.paged_attention import chunk_tile

    model, p, prompts, _, _, after_prompts, stats, _, chunks = served
    ref = _reference()
    cfg = model.config
    a = _arch(ref, cfg)
    bs, nkv = cfg.msa.block, cfg.kv_heads
    assert max(s + n for u, s, n in chunks if u == 1) == 77
    assert any(s % bs and (s + n) % bs for _, s, n in chunks)
    want = np.zeros(2, int)
    for uid, ids in prompts.items():
        n = len(ids) + (-len(ids)) % 8
        pos = jnp.arange(n)
        xs, _ = _streams(ref, a, p, ids)
        for l, x in enumerate(xs):
            w = _layer_weights(p, l)
            y = ref.rms_norm(x, w["input_layernorm"], a.rms_norm_eps)
            _, _, ki = ref.leaves_behind(a, "float32", x, w, pos)
            chosen = np.asarray(ref.chosen_blocks(a, ref.block_scores(
                a, "float32", y, ref.pooled_keys(a, ki), w), pos))
            for _, start, length in (c for c in chunks if c[0] == uid):
                # (a chunk's bucket is a block or more: hybrid_runner.
                # min_segment)
                tq = chunk_tile(max(bs, length), cfg.num_heads // nkv, bs)
                for first in range(start, start + length, tq):
                    last = min(first + tq, start + length)
                    want += [chosen[first:last].any(axis=0).sum(),
                             ((last - 1) // bs + 1) * nkv]
    names = ("msa_tile_blocks_visited", "msa_tile_blocks_visible")
    assert [stats[k] for k in names] == list(want)
    assert [after_prompts[k] for k in names] == list(want)
    assert 0 < stats[names[0]] < stats[names[1]]


def test_pooled_keys_are_the_maximum_from_scratch_after_every_step():
    """After every step of an engine whose pool is too small for its three
    requests (so one is preempted and computed again): each live sequence's
    pooled keys, read through its own block table, are the maximum over the
    tokens it has written so far, from scratch, in every layer; the newest
    block's row a maximum over part of a block. The pooled keys' rows are
    held and given back with their pages."""
    ref = _reference()
    model = _model()
    cfg, p = model.config, _params(model)
    a = _arch(ref, cfg)
    rng = np.random.default_rng(5)
    prompts = {u: rng.integers(0, 256, n).astype(np.int32)
               for u, n in ((1, 61), (2, 35), (3, 50))}
    eng = _engine(model, p, kv_blocks=21, decode_steps=3)
    eng.put(list(prompts), list(prompts.values()), max_new_tokens=14)
    toks = {u: [] for u in prompts}
    checked = partial = 0
    while eng.state.seqs or eng._queue:
        for uid, new in eng.serve_step().items():
            toks[uid].extend([new] if isinstance(new, int) else list(new))
        kc = eng.kv_cache
        held = kc.allocator.total_blocks - kc.free_blocks
        assert kc.in_use()["pooled_keys_in_use"] == held * cfg.kv_heads
        pk = np.asarray(kc.pooled)
        for seq in eng.state.seqs.values():
            n = seq.seen_tokens
            if not n:
                continue
            ids = np.concatenate([prompts[seq.uid],
                                  np.asarray(toks[seq.uid], np.int32)])[:n]
            xs, _ = _streams(ref, a, p, ids)
            for l, x in enumerate(xs):
                _, _, ki = ref.leaves_behind(
                    a, "float32", x[:n], _layer_weights(p, l), jnp.arange(n))
                ki = np.asarray(ki)
                for b, page in enumerate(seq.kv_blocks[:-(-n // 8)]):
                    np.testing.assert_allclose(
                        pk[l, page], ki[8 * b:8 * b + 8].max(axis=0),
                        atol=2e-5)
                    checked += 1
                    partial += n < 8 * b + 8
    assert eng.stats["preempted"] + eng.stats["requeued"] > 0
    assert checked > 300 and partial > 30
    # (whole pages stay in the prefix cache, their rows with them)
    assert eng.kv_cache.available_blocks == 20
    assert all(len(t) == 14 for t in toks.values())
    # and the streams are the full forward's
    for uid, prompt in prompts.items():
        seq = np.concatenate([prompt, np.asarray(toks[uid], np.int32)])
        logits = model.apply(p, jnp.asarray(seq)[None])[0]
        assert np.asarray(jnp.argmax(logits, -1))[
            len(prompt) - 1:len(seq) - 1].tolist() == toks[uid]
    eng.close()


def test_a_prefix_hit_finds_whole_blocks_pooled_keys_final():
    """The prefix cache stays on: a second prompt that shares six whole
    pages with the first skips them and reads their pooled keys as the first
    left them; its stream is the full forward's."""
    model = _model()
    p = _params(model)
    rng = np.random.default_rng(7)
    doc = rng.integers(0, 256, 52).astype(np.int32)
    eng = _engine(model, p, decode_steps=2)
    assert eng.kv_cache.prefix_cache is not None
    out = {}
    for uid in (1, 2):
        prompt = np.concatenate([doc, rng.integers(0, 256, 9 + uid)
                                 .astype(np.int32)])
        eng.put([uid], [prompt], max_new_tokens=6)
        got = [int(t) for t in eng.generate_all()[uid]]
        seq = np.concatenate([prompt, np.asarray(got, np.int32)])
        logits = model.apply(p, jnp.asarray(seq)[None])[0]
        assert np.asarray(jnp.argmax(logits, -1))[
            len(prompt) - 1:len(seq) - 1].tolist() == got
        out[uid] = eng.stats["prefix_hit_tokens"]
    assert out == {1: 0, 2: 48}
    eng.close()


@pytest.mark.parametrize("what", ["host_tier", "speculation"])
def test_what_cannot_carry_pooled_keys_refuses_by_name(what):
    model = _model()
    p = _params(model)
    kw = {"host_tier": dict(host_kv_tier=True),
          "speculation": dict(spec_decode=True)}[what]
    with pytest.raises(PooledKeysUnsupported, match="pooled keys"):
        _engine(model, p, **kw)
    with pytest.raises(ValueError, match="block size"):
        _engine(model, p, kv_block_size=16)
    with pytest.raises(LatentPoolUnsupported):
        KVCacheConfig(num_layers=1, kv_heads=1, head_dim=8, kind="latent",
                      latent_dim=8, pooled_key_dim=4)


def test_reference_refuses_what_it_does_not_write_down():
    ref = _reference()
    a = _arch(ref, _model().config)
    assert a.is_dense(0) and not a.is_dense(1) and a.dense_layers == 1
    assert a.msa_width == 5
    model = {f.name: getattr(a, f.name) for f in dataclasses.fields(a)}
    model.update(scoring_func="sigmoid", use_routing_bias=True,
                 hidden_act="swigluoai", use_qk_norm=True,
                 qk_norm_type="per_head", attention_output_gate=False,
                 n_shared_experts=1, tie_word_embeddings=False)
    assert ref.Arch.from_model(model) == a
    for key, other in (("hidden_act", "silu"), ("scoring_func", "softmax"),
                       ("attention_output_gate", True),
                       ("qk_norm_type", "per_layer")):
        with pytest.raises(ValueError, match=key):
            ref.Arch.from_model(dict(model, **{key: other}))
    with pytest.raises(ValueError, match="moe_layer_freq"):
        ref.Arch.from_model(dict(model, first_layer=5))
    with pytest.raises(NotImplementedError, match="loss_and_grads"):
        ref.loss_and_grads()


def test_the_shares_routed_parts_and_one_shared_expert_add_up_to_the_layer():
    """The eight shares of an expert layer (two of sixteen experts each),
    their routed parts added and the shared expert counted once, are the
    uncut layer: for the program's ``moe_ffn_share`` with ``swigluoai`` and
    for the reference's ``expert_block``."""
    ref = _reference()
    whole = _model(experts_held=None)
    cfg = whole.config
    p = _params(whole)
    moe = jax.tree.map(lambda a: a[1], p["layers"]["moe"])
    y = jax.random.normal(jax.random.PRNGKey(9), (24, cfg.hidden_size))
    shared = dict(moe["shared"])
    full, _ = moe_ffn_share(y, moe["router"], moe["experts"], cfg.gate,
                            shared=shared, router_bias=moe["router_bias"],
                            glu=cfg.glu)
    parts = []
    for s in range(8):
        held = jax.tree.map(lambda a: a[2 * s:2 * s + 2], moe["experts"])
        out, counts = moe_ffn_share(y, moe["router"], held, cfg.gate,
                                    offset=2 * s, router_bias=moe[
                                        "router_bias"], glu=cfg.glu)
        parts.append(out)
    # what every chip computes alike, counted once: a share with the shared
    # expert less the same share without it
    first = jax.tree.map(lambda a: a[:2], moe["experts"])
    with_shared, _ = moe_ffn_share(y, moe["router"], first, cfg.gate,
                                   shared=shared, router_bias=moe[
                                       "router_bias"], glu=cfg.glu)
    np.testing.assert_allclose(
        np.asarray(sum(parts) + with_shared - parts[0]), np.asarray(full),
        atol=2e-5)
    w = _layer_weights(p, 1)
    a_whole = _arch(ref, cfg, held=16)
    want = ref.expert_block(a_whole, "float32", y, w)
    np.testing.assert_allclose(np.asarray(full), np.asarray(want), atol=2e-5)
    ref_parts = []
    for s in range(8):
        a = _arch(ref, cfg, held=2, offset=2 * s)
        ws = dict(w, **{k: w[k][2 * s:2 * s + 2] for k in (
            "experts_gate_proj", "experts_up_proj", "experts_down_proj")})
        ref_parts.append(ref.expert_block(a, "float32", y, ws))
    shared_ref = ref.feed_forward(a_whole, "float32", y, w["shared_gate_proj"],
                                  w["shared_up_proj"], w["shared_down_proj"])
    np.testing.assert_allclose(
        np.asarray(sum(ref_parts) - 7 * shared_ref), np.asarray(want),
        atol=2e-5)
