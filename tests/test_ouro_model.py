"""The looped stack (``ouro-2.6b``: ``models/transformer.py`` with ``ut_steps``
passes of the same layers, post-branch norms and an exit gate) on the CPU at a
toy size (``tiny-ouro``: three layers run four times, a K/V pool of twelve
layer slots), float32, seeded: the no-cache forward and its exit distribution
against the plain reference; the engine (prefill in chunks that end inside
blocks, token steps single and in bursts, through the pool) against the
reference's full forward; preemption and recomputation; a prefix hit; the
stack run once with no post-norms is ``tiny``'s program to the letter; what is
refused, by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine_v2, model_runner
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models.moe_transformer import MoETransformerConfig
from deepspeed_tpu.models.transformer import (EarlyExitUnsupported,
                                              LoopedStackUnsupported,
                                              TransformerConfig)
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

F32 = jnp.float32
# float32 against float32 at ``highest``: what is left is the order of the
# sums (the engine's softmax over pages, the reference's over the sequence).
# bfloat16 anywhere in the stack reads 1e-2 and more on these logits.
ATOL = 3e-5


def _model(**kw):
    return get_model("tiny-ouro", param_dtype=F32, dtype=F32, **kw)


def _reference():
    from benchmarks.harness import manifest as mf

    return mf.load_module("references", "ouro")


def _arch(ref, cfg):
    return ref.Arch.from_model(dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        intermediate_size=cfg.ffn, vocab_size=cfg.vocab_size,
        num_hidden_layers=cfg.num_layers, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.norm_eps, total_ut_steps=cfg.ut_steps,
        early_exit_threshold=cfg.early_exit_threshold, hidden_act="silu",
        tie_word_embeddings=False))


SEED = 2**31 + 11


@pytest.fixture(scope="module")
def drawn():
    """The model, the reference's sizes, and one seed's weights as the
    benchmark draws them (norm gains off one, a gate that does not
    saturate): the program's tree, and the same arrays under the published
    names, a layer at a time."""
    from benchmarks.harness import weights

    model, ref = _model(), _reference()
    a = _arch(ref, model.config)
    return (model, ref, a, weights.make_program_params(a, SEED, F32),
            weights.reference_layer_fn(a, SEED, F32),
            weights.reference_top(a, SEED, F32))


def _engine(model, params, **kw):
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    kw = dict(dict(kv_block_size=8, kv_blocks=64, max_tokens_per_step=20,
                   max_seqs_per_step=4, max_blocks_per_seq=16), **kw)
    return InferenceEngineV2(model, mesh=mesh, params=params, dtype=F32, **kw)


def test_presets_are_the_published_stack_and_its_toy():
    c = get_model("ouro-2.6b").config
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.head_dim,
            c.ffn, c.vocab_size, c.max_seq_len) == (
                2048, 48, 16, 16, 128, 5632, 49152, 65536)
    assert (c.ut_steps, c.post_norms, c.early_exit_threshold, c.rope_theta,
            c.norm_eps, c.tie_embeddings) == (4, True, 1.0, 1e6, 1e-6, False)
    # ISSUE 52's arithmetic: 51.39 M a layer, 2.668 B in all
    assert c._layer_params() == 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048
    assert abs(c.num_params() - 2.668e9) < 1e6
    spec, beside = model_runner.store_specs(
        c, kv_blocks=337, kv_block_size=16, max_seqs=16, state_slots=None,
        dtype=jnp.bfloat16, quant_bits=None)
    assert spec.num_layers == 192 and beside == []
    assert model_runner.passes_per_token(c) == 4
    t = _model().config
    assert (t.num_layers, t.ut_steps, t.post_norms) == (3, 4, True)
    shapes = jax.eval_shape(_model().init, jax.random.PRNGKey(0))
    assert set(shapes["layers"]) == {"attn", "mlp", "ln1", "ln2", "ln1_post",
                                     "ln2_post"}
    assert shapes["exit_gate"]["kernel"].shape == (64, 1)
    assert shapes["exit_gate"]["bias"].shape == (1,)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        _model().logical_axes(), is_leaf=lambda x: isinstance(x, tuple))


def test_no_cache_forward_and_exit_distribution_match_the_reference(drawn):
    model, ref, a, params, layer_fn, top = drawn
    toks = np.random.default_rng(3).integers(0, 256, (2, 37)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, probs = model.apply_with_exit(params, jnp.asarray(toks))
        plain = model.apply(params, jnp.asarray(toks))
    want, want_p = ref.forward_logits(a, list(toks), [np.arange(37)] * 2,
                                      layer_fn, top, exit_probs=True)
    np.testing.assert_allclose(logits, np.stack(want), atol=ATOL)
    np.testing.assert_array_equal(plain, logits)
    # a distribution over the four passes, no pass's share saturated: the
    # tolerance is float32's on a probability (bfloat16 reads 4e-3)
    np.testing.assert_allclose(probs, np.stack(want_p), atol=2e-6)
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, atol=1e-6)
    assert probs.shape == (2, 37, 4)
    assert 0.005 < float(probs.min()) and float(probs.max()) < 0.95


@pytest.fixture(scope="module")
def served(drawn):
    """Two prompts through the engine: chunks of at most 20 tokens (they end
    inside blocks of 8), single token steps, then bursts of four; the rows
    the engine sampled from and the counters at the end."""
    model, _, _, params, _, _ = drawn
    rng = np.random.default_rng(2)
    prompts = {1: rng.integers(0, 256, 77).astype(np.int32),
               2: rng.integers(0, 256, 13).astype(np.int32)}
    eng = _engine(model, params, decode_steps=1)
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
    stats, pool = dict(eng.stats), eng.kv_cache.kv_state["kv"].shape
    eng.close()
    return prompts, toks, got, stats, pool


def test_engine_chunks_steps_and_bursts_match_the_reference(drawn, served):
    _, ref, a, _, layer_fn, top = drawn
    prompts, toks, got, stats, pool = served
    assert pool[0] == a.cache_layers == 12 and stats["kv_slots"] == 12
    seqs = {uid: np.concatenate([prompts[uid],
                                 np.asarray(toks[uid][:-1], np.int32)])
            for uid in prompts}
    want = ref.forward_logits(
        a, list(seqs.values()),
        [np.arange(len(prompts[u]) - 1, len(seqs[u])) for u in seqs],
        layer_fn, top)
    for uid, w in zip(seqs, want):
        assert len(got[uid]) >= 6 and len(toks[uid]) == 12
        for j, row in got[uid]:
            np.testing.assert_allclose(row, np.asarray(w[j]), atol=ATOL)
        # the bursts hand out ids alone
        assert [int(r.argmax()) for r in np.asarray(w)] == toks[uid]
    assert stats["calls_gather"] == 0 < stats["calls_multi_decode"]
    assert stats["calls_prefill"] >= 5 and stats["calls_decode"] >= 5
    # every row of every program ran every pass
    for program in ("prefill", "decode", "multi_decode"):
        assert stats[f"ut_passes_{program}"] == 4 * stats[f"rows_{program}"] > 0


def _greedy(model, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = model.apply(params, jnp.asarray([seq]))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


@pytest.mark.parametrize("host_tier", [False, True],
                         ids=["recompute", "host-tier"])
def test_an_engine_that_preempts_serves_the_same_tokens(drawn, host_tier):
    """A pool too small for its three requests: one is preempted and its
    prompt and tokens so far computed again, through every pass's slots; or,
    with the host tier, its pages go out and come back as they are, twelve
    slots a page (the tier moves ``[slots, blocks, ...]`` and does not ask
    what a slot is)."""
    model, _, _, params, _, _ = drawn
    rng = np.random.default_rng(5)
    prompts = {u: rng.integers(0, 256, n).astype(np.int32)
               for u, n in ((1, 61), (2, 35), (3, 50))}
    eng = _engine(model, params, kv_blocks=21, decode_steps=3,
                  host_kv_tier=host_tier)
    eng.put(list(prompts), list(prompts.values()), max_new_tokens=14)
    toks = {u: [int(t) for t in ts] for u, ts in eng.generate_all().items()}
    if host_tier:
        assert eng.stats["paged_out"] == eng.stats["paged_in"] > 0
    else:
        assert eng.stats["preempted"] + eng.stats["requeued"] > 0
    for uid, prompt in prompts.items():
        seq = np.concatenate([prompt, np.asarray(toks[uid], np.int32)])
        logits = model.apply(params, jnp.asarray(seq)[None])[0]
        assert np.asarray(jnp.argmax(logits, -1))[
            len(prompt) - 1:len(seq) - 1].tolist() == toks[uid]
    eng.close()


def test_a_prefix_hit_equals_a_fresh_prompt(drawn):
    """Whole pages of a shared document are taken from the prefix cache, in
    all twelve slots at once (a page is a page of every slot)."""
    model, _, _, params, _, _ = drawn
    rng = np.random.default_rng(7)
    doc = rng.integers(0, 256, 52).astype(np.int32)
    eng = _engine(model, params, decode_steps=2)
    hits = {}
    for uid in (1, 2):
        prompt = np.concatenate([doc, rng.integers(0, 256, 9 + uid)
                                 .astype(np.int32)])
        eng.put([uid], [prompt], max_new_tokens=6)
        got = [int(t) for t in eng.generate_all()[uid]]
        assert got == _greedy(model, params, prompt, 6)
        hits[uid] = eng.stats["prefix_hit_tokens"]
    assert hits == {1: 0, 2: 48}
    eng.close()


T, S, BM, NB, BS = 16, 4, 8, 32, 8


def _lowered(cfg, params):
    ids = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    kv = {"kv": jax.ShapeDtypeStruct(
        (cfg.ut_steps * cfg.num_layers, NB, BS, 2, cfg.kv_heads,
         cfg.head_dim), F32)}
    fns = engine_v2._shared_step_fns(cfg, None)
    return {
        "gather": fns["step"].lower(params, kv, ids(T), ids(T), ids(T),
                                    ids(S, BM), ids()),
        "prefill": fns["prefill"].lower(params, kv, ids(2, 8), ids(2),
                                        ids(2), ids(2, BM)),
        "decode": fns["decode"].lower(params, kv, ids(S), ids(S),
                                      ids(S, BM), ids(S)),
        "multi_decode": fns["multi_decode"].lower(
            params, kv, ids(S), ids(S), ids(S, BM), ids(S), steps=3)}


def test_one_pass_without_post_norms_is_tinys_program_to_the_letter():
    """``ut_steps`` 1 and ``post_norms`` off leave the four dense programs
    as they were: the same text as ``tiny``'s, no scope of the loop in it;
    and ``tiny-ouro`` cut to one pass with no post-norms serves the tokens
    of the plain stack it then is."""
    tiny = get_model("tiny")
    same = get_model("tiny", ut_steps=1, post_norms=False,
                     early_exit_threshold=1.0)
    shapes = jax.eval_shape(tiny.init, jax.random.PRNGKey(0))
    a, b = _lowered(tiny.config, shapes), _lowered(same.config, shapes)
    for program in a:
        assert a[program].as_text() == b[program].as_text()
        text = a[program].as_text(debug_info=True)
        assert "ut_pass" not in text and "pass_norm" not in text
    once = _model(ut_steps=1, post_norms=False)
    assert "exit_gate" not in jax.eval_shape(once.init, jax.random.PRNGKey(0))
    params = once.init(jax.random.PRNGKey(1))
    prompt = np.random.default_rng(1).integers(0, 256, 21).astype(np.int32)
    eng = _engine(once, params, decode_steps=2)
    assert eng.stats["kv_slots"] == 3
    eng.put([1], [prompt], max_new_tokens=5)
    assert [int(t) for t in eng.generate_all()[1]] == _greedy(
        once, params, prompt, 5)
    assert eng.stats["ut_passes_prefill"] == eng.stats["rows_prefill"] > 0
    eng.close()


def test_what_is_refused_is_refused_by_name(drawn):
    model, ref, a, params, _, _ = drawn
    cfg = model.config
    with pytest.raises(EarlyExitUnsupported, match="early_exit_threshold"):
        _model(early_exit_threshold=0.9)
    with pytest.raises(LoopedStackUnsupported, match="objective"):
        model.loss(params, {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(LoopedStackUnsupported, match="InferenceEngineV2"):
        model_runner.forward_with_cache(
            cfg, params, jnp.zeros((1, 4), jnp.int32),
            model_runner.init_dense_cache(cfg, 1, 8, F32), 0)
    with pytest.raises(LoopedStackUnsupported, match="parallel_block"):
        _model(parallel_block=True)
    with pytest.raises(LoopedStackUnsupported, match="param_host_offload"):
        get_model("tiny-ouro", param_host_offload=True).apply(
            params, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(LoopedStackUnsupported, match="once a token"):
        get_model("tiny").apply_with_exit(None, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(LoopedStackUnsupported, match="expert stack"):
        MoETransformerConfig(ut_steps=2)
    with pytest.raises(ValueError, match="ut_steps"):
        TransformerConfig(ut_steps=0)
    with pytest.raises(ValueError, match="threshold 1"):
        ref.Arch.from_model(dict(dataclasses.asdict(a),
                                 early_exit_threshold=0.5))
    with pytest.raises(ValueError, match="hidden_act"):
        ref.Arch.from_model(dict(dataclasses.asdict(a), hidden_act="gelu"))
    with pytest.raises(NotImplementedError, match="loss_and_grads"):
        ref.loss_and_grads()
