"""The third architecture (MiniCPM-SALA: lightning attention beside
block-sparse attention) at a toy size on the CPU: the two forms of the
lightning recurrence against the token scan, the block-selection rule, decode
over chosen pages and the chunk form against the masked-dense form, the
compressed keys across chunk borders, and the engine (chunked prefill, then
decode through the three caches) against the full forward, across
``dense_len``, with preemption, and with its refusals."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import hybrid_runner
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops import block_sparse
from deepspeed_tpu.ops.pallas import gated_delta
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

F32 = jnp.float32


def _scan(q, k, v, g, state):
    """``S <- e^g S + k v^T; o = S^T q``, one token after another."""
    def step(S, t):
        q_t, k_t, v_t, g_t = t
        S = jnp.exp(g_t)[..., None, None] * S + k_t[..., :, None] * v_t[..., None, :]
        return S, jnp.einsum("bnkv,bnk->bnv", S, q_t)

    S, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g)))
    return jnp.moveaxis(o, 0, 1), S


def _qkvg(T, n=4, d=32, B=2, seed=0, rate=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (B, T, n, d), F32) for i in range(3))
    g = -rate * jax.random.uniform(ks[3], (B, T, n), F32)
    S = jax.random.normal(ks[4], (B, n, d, d), F32)
    return q, k, v, g, S


# -- configuration ----------------------------------------------------------


def test_the_published_preset_cut_to_the_stage_held_here():
    c = get_model("minicpm-sala", num_layers=8, first_layer=9).config
    assert c.layer_kinds == (True,) + (False,) * 6 + (True,)
    assert c.stack_plan == (1, ((True, 1), (False, 6), (True, 1)))
    assert (c.kv_layers, c.recurrent_layers, c.conv_taps) == (2, 6, 1)
    assert abs(c.residual_scale - 1.4 / math.sqrt(32)) < 1e-12
    assert c.logit_divisor == 16 and c.scale_emb == 12
    assert c.sparse == block_sparse.SparseSizes(32, 16, 64, 1, 2048, 64, 8192)
    whole = get_model("minicpm-sala").config
    assert sum(whole.layer_kinds) == 8 and whole.layer_kinds[0]
    assert [l for l, m in enumerate(whole.layer_kinds) if m] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    # what the engine keeps of the stage: 2,820 M parameters
    served = jax.eval_shape(lambda p: hybrid.serving_params(c, p),
                            jax.eval_shape(get_model(
                                "minicpm-sala", num_layers=8,
                                first_layer=9).init, jax.random.PRNGKey(0)))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(served))
    assert abs(n / 1e6 - 2820.6) < 0.5, n


def test_qwen3_next_is_one_way_to_fill_the_layer_kinds():
    c = get_model("qwen3-next-80b-a3b").config
    assert c.layer_kinds == ((False,) * 3 + (True,)) * 12
    assert c.stack_plan == (12, ((False, 3), (True, 1)))
    assert (c.periods, c.kv_layers, c.recurrent_layers) == (12, 12, 36)
    assert c.sparse is None and c.conv_taps == 4


def test_a_pattern_that_does_not_hold_the_layers_is_refused():
    with pytest.raises(ValueError, match="lie outside the pattern"):
        get_model("minicpm-sala", num_layers=8, first_layer=30)
    with pytest.raises(ValueError, match="cannot hold"):
        get_model("tiny-sala", sparse_topk=3)


def test_the_decay_is_a_constant_of_head_and_published_layer():
    c = get_model("minicpm-sala", num_layers=8, first_layer=9).config
    g = np.asarray(c.lightning_decay())
    assert g.shape == (6, 32)
    want = -(2.0 ** (-8 * 1 / 32)) * (1 - 10 / 31 + 1e-5)   # head 0, layer 10
    assert abs(g[0, 0] - want) < 1e-6
    assert np.all(np.diff(g, axis=1) > 0)      # later heads forget more slowly
    assert np.all(np.diff(g, axis=0) > 0)      # and so do later layers


# -- the lightning recurrence -----------------------------------------------


@pytest.mark.parametrize("T", [1, 63, 64, 130])
def test_lightning_chunk_agrees_with_the_token_scan(T):
    q, k, v, g, S = _qkvg(T)
    want_o, want_S = _scan(q, k, v, g, S)
    o, S1 = gated_delta.lightning_chunk(q, k, v, g, S)
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S1, want_S, rtol=2e-5, atol=2e-5)


def test_lightning_chunk_padding_neither_decays_nor_writes():
    q, k, v, g, S = _qkvg(40)
    real = jnp.arange(40) < 25
    gm = jnp.where(real[None, :, None], g, 0.0)
    km = jnp.where(real[None, :, None, None], k, 0.0)
    _, S_pad = gated_delta.lightning_chunk(q, km, v, gm, S)
    _, S_cut = gated_delta.lightning_chunk(q[:, :25], k[:, :25], v[:, :25],
                                           g[:, :25], S)
    np.testing.assert_allclose(S_pad, S_cut, rtol=1e-5, atol=1e-5)


def test_lightning_chunk_with_a_head_that_forgets_in_a_token():
    """``a^128`` underflows: the pairwise decay comes from the difference."""
    q, k, v, g, S = _qkvg(200, rate=0.0)
    g = g - 2.0                                  # e^-2 a token: e^-400 over 200
    o, S1 = gated_delta.lightning_chunk(q, k, v, g, S)
    want_o, want_S = _scan(q, k, v, g, S)
    assert np.all(np.isfinite(o)) and np.all(np.isfinite(S1))
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(S1, want_S, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("heads", [4, 32])
def test_lightning_decode_updates_its_slots_of_the_pool_in_place(heads):
    d, B = 32, 3
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    pool = jax.random.normal(ks[0], (2, 5, heads, d, d), F32)
    q, k, v = (jax.random.normal(ks[i], (B, heads, d), F32) for i in (1, 2, 3))
    a = jax.random.uniform(ks[4], (B, heads), F32, 0.2, 0.99)
    slots = jnp.asarray([3, 0, 4])
    o, new = gated_delta.lightning_decode(pool, 1, slots, q, k, v, a)
    S = a[..., None, None] * pool[1, slots] + k[..., :, None] * v[..., None, :]
    np.testing.assert_allclose(new[1, slots], S, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(o, jnp.einsum("bnkv,bnk->bnv", S, q),
                               rtol=1e-5, atol=1e-5)
    untouched = np.asarray(new).copy()
    untouched[1, np.asarray(slots)] = np.asarray(pool)[1, np.asarray(slots)]
    np.testing.assert_array_equal(untouched, pool)


# -- the block-selection rule -----------------------------------------------

SZ = block_sparse.SparseSizes(kernel=8, stride=4, block=16, init_blocks=1,
                              window=32, topk=5, dense_len=64)


def _select(t, seed=0, W=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(ks[0], (1, 2, 2, 32), F32)
    ck = jax.random.normal(ks[1], (W, 2, 32), F32)
    return block_sparse.select_blocks(SZ, q, ck, jnp.asarray([t]),
                                      1 / math.sqrt(32)), q, ck


@pytest.mark.parametrize("t", [7, 40, 100, 255])
def test_selection_keeps_the_first_block_and_the_window(t):
    (idx, count, visible), _, _ = _select(t)
    n = t + 1
    seen = -(-n // 16)
    assert int(visible[0]) == seen
    assert np.all(np.asarray(count) == min(5, seen))
    for h in range(2):
        chosen = [int(i) for i in np.asarray(idx[0, h, :int(count[0, h])])]
        assert chosen == sorted(chosen) and chosen[-1] == t // 16
        assert 0 in chosen
        assert set(range(max(n - 32, 0) // 16, seen)) <= set(chosen)
        assert np.all(np.asarray(idx[0, h, int(count[0, h]):]) == 16)


def test_selection_takes_the_best_of_the_rest_and_breaks_ties_low():
    """The rule recomputed by hand for one query: window scores, the
    blocks' maxima over the windows that overlap them, forced blocks, then
    the highest; equal scores go to the lower block."""
    t = 255
    (idx, count, _), q, ck = _select(t, seed=3)
    s = np.einsum("kgd,wkd->kgw", np.asarray(q[0]), np.asarray(ck)) / math.sqrt(32)
    whole = 4 * np.arange(64) + 8 <= t + 1
    p = np.exp(s - s[..., whole].max(-1, keepdims=True)) * whole
    r = (p / p.sum(-1, keepdims=True)).sum(1)                     # [k, W]
    for h in range(2):
        score = np.full(16, -1.0)
        for i in range(16):
            js = [j for j in range(64) if whole[j] and 4 * j < 16 * i + 16
                  and 4 * j + 8 > 16 * i]
            if js:
                score[i] = max(r[h, j] for j in js)
        forced = {0} | set(range((t + 1 - 32) // 16, 16))
        rest = sorted((i for i in range(16) if i not in forced),
                      key=lambda i: (-score[i], i))[:5 - len(forced)]
        assert sorted(forced | set(rest)) == [
            int(i) for i in np.asarray(idx[0, h])]
    # ties: equal compressed keys everywhere, so every free block scores alike
    flat = jnp.ones((64, 2, 32), F32)
    idx, _, _ = block_sparse.select_blocks(SZ, q, flat, jnp.asarray([t]), 1.0)
    assert [int(i) for i in np.asarray(idx[0, 0])] == [0, 1, 2, 14, 15]


# -- attention over chosen pages, against the masked-dense form ---------------


@pytest.fixture(scope="module")
def tiny():
    model = get_model("tiny-sala", param_dtype=F32, dtype=F32)
    params = model.init(jax.random.PRNGKey(0))
    # QK-norm gains away from one, so that the blocks' scores differ
    attn = params["layers"]["attn"]
    for name, key in (("q_norm", 5), ("k_norm", 6)):
        attn[name] = 1.5 * jax.random.normal(jax.random.PRNGKey(key),
                                             attn[name].shape, F32)
    return model, params


def _pooled(cfg, k, v, n, blocks=24, table_len=16, seed=0):
    """One sequence's keys and values scattered over shuffled pages of a
    pool, with its compressed keys written chunk by chunk."""
    bs, sz = cfg.sparse.block, cfg.sparse
    table = np.random.default_rng(seed).permutation(blocks - 1)[:table_len]
    kv = jnp.zeros((1, blocks, bs, 2, cfg.kv_heads, cfg.head_dim), F32)
    pos = np.arange(n)
    kv = kv.at[0, table[pos // bs], pos % bs].set(jnp.stack([k, v], axis=1))
    ck = jnp.zeros((1, blocks, sz.per_block, cfg.kv_heads, cfg.head_dim), F32)
    bt = jnp.asarray(table, jnp.int32)[None]
    ck = hybrid_runner._compress_new(sz, kv, ck, 0, bt, jnp.asarray([0]),
                                     jnp.asarray([n]), n // sz.stride + 1)
    return kv, ck, bt


@pytest.mark.parametrize("n", [40, 64, 65, 130, 250])
def test_decode_over_chosen_pages_agrees_with_masked_dense(tiny, n):
    cfg = tiny[0].config
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    q = jax.random.normal(ks[0], (1, n, cfg.num_heads, cfg.head_dim), F32)
    k = jax.random.normal(ks[1], (n, cfg.kv_heads, cfg.head_dim), F32)
    v = jax.random.normal(ks[2], (n, cfg.kv_heads, cfg.head_dim), F32)
    want = hybrid.full_attention(cfg, q, k[None], v[None],
                                 jnp.arange(n)[None])[0, -1]
    kv, ck, bt = _pooled(cfg, k, v, n)
    got, counts = hybrid_runner._sparse_decode(
        cfg, None, q[0, -1:], kv, ck, 0, bt, jnp.asarray([n]))
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-5)
    sparse = n - 1 >= cfg.sparse.dense_len
    seen = -(-n // 16)
    assert [int(c) for c in counts] == (
        [2 * min(5, seen), 2 * seen, 0] if sparse else [0, 0, 1])


@pytest.mark.parametrize("pos0,tq", [(0, 64), (48, 64), (60, 128), (128, 64)])
def test_chunk_attention_agrees_with_masked_dense(tiny, pos0, tq):
    cfg = tiny[0].config
    n = pos0 + tq - 3                               # three padded rows
    ks = jax.random.split(jax.random.PRNGKey(pos0 + tq), 3)
    q = jax.random.normal(ks[0], (1, n, cfg.num_heads, cfg.head_dim), F32)
    k = jax.random.normal(ks[1], (n, cfg.kv_heads, cfg.head_dim), F32)
    v = jax.random.normal(ks[2], (n, cfg.kv_heads, cfg.head_dim), F32)
    want = hybrid.full_attention(cfg, q, k[None], v[None],
                                 jnp.arange(n)[None])[0, pos0:]
    kv, ck, bt = _pooled(cfg, k, v, n)
    pos = pos0 + jnp.arange(tq)[None]
    qc = jnp.pad(q[:, pos0:], ((0, 0), (0, 3), (0, 0), (0, 0)))
    got, _ = hybrid_runner._sparse_prefill(
        cfg, qc, kv, ck, 0, bt, pos, pos < n, jnp.asarray([n]))
    np.testing.assert_allclose(got[0, :tq - 3], want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cuts", [(5, 16, 23), (64,), (7, 8, 9, 70), (31, 33)])
def test_compressed_keys_across_chunk_borders(tiny, cuts):
    """Chunks that split a window and a block leave the same compressed
    keys as one pass: window ``j`` is written when its last token is."""
    cfg = tiny[0].config
    sz, n = cfg.sparse, 100
    k = jax.random.normal(jax.random.PRNGKey(9), (n, cfg.kv_heads,
                                                  cfg.head_dim), F32)
    kv, whole, bt = _pooled(cfg, k, k, n)
    ck = jnp.zeros_like(whole)
    edges = (0,) + cuts + (n,)
    for a, b in zip(edges[:-1], edges[1:]):
        ck = hybrid_runner._compress_new(
            sz, kv, ck, 0, bt, jnp.asarray([a]), jnp.asarray([b - a]),
            (b - a) // sz.stride + 1)
    scratch = ck.shape[1] - 1
    np.testing.assert_allclose(ck[:, :scratch], whole[:, :scratch], atol=1e-6)
    W = (n - sz.kernel) // sz.stride + 1
    table = np.asarray(bt[0])
    for j in (0, 3, 4, W - 1):
        want = k[sz.stride * j:sz.stride * j + sz.kernel].mean(0)
        page, slot = table[sz.stride * j // sz.block], j % sz.per_block
        np.testing.assert_allclose(ck[0, page, slot], want, atol=1e-6)
    assert not np.any(np.asarray(ck[0, table[(sz.stride * W) // sz.block],
                                    W % sz.per_block]))   # not whole: unwritten


# -- the engine ---------------------------------------------------------------


def _engine(model, params, **kw):
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    args = dict(kv_blocks=64, kv_block_size=16, max_tokens_per_step=32,
                max_seqs_per_step=4, max_blocks_per_seq=14, state_slots=5,
                decode_steps=4)
    args.update(kw)
    return InferenceEngineV2(model, mesh=mesh, params=params, dtype=F32, **args)


PROMPTS = (100, 57, 70)            # past dense_len (64), crossing it while
NEW = 24                           # decoding, and just past it


def _prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 256, n).astype(np.int32) for n in PROMPTS]


@pytest.fixture(scope="module")
def served(tiny):
    model, params = tiny
    eng = _engine(model, params)
    eng.put([1, 2, 3], _prompts(), max_new_tokens=NEW)
    during = []
    out = {}
    while len(out) < 3 or any(len(v) < NEW for v in out.values()):
        for uid, toks in eng.serve_step().items():
            out.setdefault(uid, []).extend(toks)
        kc = eng.kv_cache
        during.append((kc.allocator.total_blocks - kc.free_blocks,
                       kc.in_use()["compressed_keys_in_use"]))
    return eng, out, during


def test_engine_streams_equal_the_full_forward_across_dense_len(tiny, served):
    """Chunked prefill (32-token chunks of one sequence), then decode through
    the paged pool, the compressed keys and the state pool."""
    model, params = tiny
    _, out, _ = served
    for uid, p in zip((1, 2, 3), _prompts()):
        seq = np.concatenate([p, np.asarray(out[uid], np.int32)])
        logits = model.apply(params, jnp.asarray(seq)[None])[0]
        want = np.asarray(jnp.argmax(logits, -1))[len(p) - 1:len(seq) - 1]
        np.testing.assert_array_equal(want, out[uid])


def test_a_step_with_a_chunk_and_decoding_sequences_runs_no_gather(served):
    eng, _, _ = served
    st = eng.stats
    assert st["tokens_gather"] == 0 and st["prefill_gather_fallbacks"] == 0
    assert st["tokens_prefill_kernel"] == 3 and st["prefill_chunk_calls"] >= 8
    # every step with a chunk went through the prefill program, one
    # sequence a call
    assert st["calls_gather"] == 0 < st["prefill_kernel_steps"]
    assert st["prefill_kernel_steps"] <= st["calls_prefill"]
    assert st["calls_prefill"] == st["prefill_chunk_calls"]
    # some step made a call of each of the two programs
    assert (st["calls_prefill"] + st["calls_decode"]
            + st["calls_multi_decode"]) > st["steps_dispatched"]
    assert st["tokens_decode"] > 0 and st["tokens_multi_decode"] > 0
    # the rule ran: it read fewer blocks than it saw, and the short
    # positions read everything
    assert 0 < st["sparse_blocks_selected"] < st["sparse_blocks_visible"]
    assert st["sparse_dense_tokens"] > 0


def test_compressed_keys_are_held_and_freed_with_their_pages(served):
    eng, _, during = served
    assert max(c for _, c in during) > 0
    assert all(c == held * 4 for held, c in during)
    kc = eng.kv_cache
    assert kc.in_use()["compressed_keys_in_use"] == 0
    assert kc.free_blocks == kc.allocator.total_blocks
    assert eng.kv_cache.state_pool.slots_in_use == 0
    assert eng.stats["compressed_keys_in_use"] == during[-2][1]


def test_preemption_by_recompute_leaves_the_streams_as_they_were(tiny, served):
    model, params = tiny
    _, want, _ = served
    eng = _engine(model, params, kv_blocks=17)     # 16 pages: not all three fit
    eng.put([1, 2, 3], _prompts(), max_new_tokens=NEW)
    out = eng.generate_all()
    assert eng.stats["preempted"] + eng.stats["requeued"] > 0
    assert {k: list(v) for k, v in out.items()} == want


def test_engine_agrees_with_the_plain_reference_on_seeded_weights():
    """The benchmark's reference (float32, the recurrence a token scan, the
    rule a mask) on the fixture configuration's seeded weights."""
    import json
    import os

    from benchmarks.harness import manifest as mf
    from benchmarks.harness import weights

    fx = os.path.join(os.path.dirname(__file__), "benchmarks", "fixtures")
    with open(os.path.join(fx, "configs", "tiny-sala-serve-c1.json")) as f:
        cfg = json.load(f)
    ref = mf.reference_of(cfg, fx)
    arch = ref.Arch.from_model(cfg)
    model = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                      max_seq_len=256, param_dtype=F32, dtype=F32,
                      **cfg["preset_overrides"])
    params = weights.make_program_params(arch, 11, F32)
    eng = _engine(model, params)
    prompts = _prompts()[:2]
    eng.put([1, 2], prompts, max_new_tokens=NEW)
    out = eng.generate_all()
    seqs = [np.concatenate([p, np.asarray(out[u], np.int32)])
            for u, p in zip((1, 2), prompts)]
    rows = [np.arange(len(p) - 1, len(s) - 1) for p, s in zip(prompts, seqs)]
    want = ref.forward_logits(
        arch, [np.pad(s, (0, 224 - len(s))) for s in seqs], rows,
        weights.reference_layer_fn(arch, 11, F32),
        weights.reference_top(arch, 11, F32))
    for u, w in zip((1, 2), want):
        np.testing.assert_array_equal(np.argmax(np.asarray(w), -1), out[u])


@pytest.mark.parametrize("what", ["host_kv_tier", "spec_decode", "page_out",
                                  "migrate_out_session", "page_size",
                                  "gather_program"])
def test_what_the_architecture_refuses_by_name(tiny, what):
    from deepspeed_tpu.inference.ragged import StateSnapshotUnsupported

    model, params = tiny
    if what in ("host_kv_tier", "spec_decode"):
        with pytest.raises(StateSnapshotUnsupported):
            _engine(model, params, **{what: True})
    elif what == "page_size":
        with pytest.raises(ValueError, match="pages of its block size, 16"):
            _engine(model, params, kv_block_size=8)
    elif what == "gather_program":
        with pytest.raises(NotImplementedError, match="context per token"):
            hybrid_runner.ragged_forward(model.config, None, None, *[None] * 6)
    else:
        eng = _engine(model, params)
        assert eng.kv_cache.prefix_cache is None
        eng.put([1], _prompts()[:1], max_new_tokens=4)
        eng.serve_step()
        with pytest.raises(StateSnapshotUnsupported):
            getattr(eng, what)(1)
