"""Program against reference for the hybrid architecture, on the CPU at a
small size (hidden 64, 2 periods of 3 recurrent : 1 full layers, 16 experts
of which 4 are held from offset 4), float32 on seeded weights drawn by
``harness/weights.py`` for both sides: each kind of layer, the chunked
recurrence against the token-by-token one, the expert shares against the
uncut layer, and prefill then decode through both pools — on logits."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import compare, weights
from benchmarks.references import qwen3_next as ref
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops.pallas.gated_delta import gdn_chunk, gdn_decode
from deepspeed_tpu.parallel.moe import moe_ffn_share

HERE = os.path.dirname(os.path.abspath(__file__))
SEED, T = 11, 50
F32 = jnp.float32


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "fixtures", "configs",
                           "tiny-hybrid-serve-c1.json")) as f:
        cfg = json.load(f)
    arch = ref.Arch.from_model(cfg)
    model = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                      max_seq_len=256, param_dtype=F32, dtype=F32,
                      **cfg["preset_overrides"])
    params = weights.make_program_params(arch, SEED, F32)
    return {"cfg": cfg, "arch": arch, "model": model, "params": params,
            "serving": hybrid.serving_params(model.config, params),
            "layer": weights.reference_layer_fn(arch, SEED, F32),
            "top": weights.reference_top(arch, SEED, F32)}


def _x(tiny, n=T, key=0):
    h = tiny["arch"].hidden_size
    return jax.random.normal(jax.random.PRNGKey(key), (n, h), F32)


def _program_recurrent(c, gp, y):
    """The program's recurrent mixer on one sequence from an empty state:
    projections, convolution, the *chunked* recurrence, output."""
    mixed, z, beta, g = hybrid.gdn_project(c, gp, y[None])
    tail = jnp.zeros((1, c.linear_conv_kernel_dim - 1, c.conv_channels), F32)
    conv, _ = hybrid.causal_conv(gp["conv"], tail, mixed)
    q, k, v = hybrid.gdn_heads(c, conv)
    s0 = jnp.zeros((1, c.linear_num_value_heads, c.linear_key_head_dim,
                    c.linear_value_head_dim), F32)
    o, _ = gdn_chunk(q, k, v, g, beta, s0, chunk=16)
    return hybrid.gdn_output(c, gp, o, z)[0]


@pytest.mark.parametrize("layer", [0, 2, 5])
def test_recurrent_layer_matches_the_token_by_token_reference(tiny, layer):
    c, a = tiny["model"].config, tiny["arch"]
    w = tiny["layer"](layer)
    y = ref.rms_norm(_x(tiny), w["input_layernorm"], a.rms_norm_eps)
    want = ref.recurrent_mixer(a, "float32", y, w)
    gp = jax.tree.map(lambda t: t[layer - layer // 4], tiny["serving"]["gdn"])
    assert compare.rel_l2(_program_recurrent(c, gp, y), want) < 1e-5


def _recurrence_inputs(n, heads=3, d=32, key=1):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = ref.l2_normalise(jax.random.normal(ks[0], (n, heads, d))) / np.sqrt(d)
    k = ref.l2_normalise(jax.random.normal(ks[1], (n, heads, d)))
    v = jax.random.normal(ks[2], (n, heads, d))
    # decays from nearly none to a state forgotten within a token
    g = -jnp.exp(2.0 * jax.random.normal(ks[3], (n, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (n, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("n,cut", [(1, 0), (63, 0), (64, 0), (65, 0),
                                   (150, 0), (150, 70), (150, 64), (97, 1)])
def test_chunked_recurrence_equals_the_token_form(n, cut):
    """At lengths that are and are not multiples of the chunk, and (``cut``)
    in two calls, the second from the state the first left."""
    q, k, v, g, beta = _recurrence_inputs(n)
    want = ref.delta_rule(q, k, v, g, beta)
    args = [t[None] for t in (q, k, v, g, beta)]
    s = jnp.zeros((1, q.shape[1], q.shape[2], v.shape[2]), F32)
    outs = []
    for lo, hi in ((0, cut), (cut, n)):
        if hi > lo:
            o, s = gdn_chunk(*[t[:, lo:hi] for t in args], s, chunk=64)
            outs.append(o[0])
    # float32 rounding through a 64-row triangular solve, at key size 32
    assert compare.rel_l2(jnp.concatenate(outs), want) < 1e-4


def test_decode_kernel_equals_the_token_form_and_touches_only_its_slots():
    n, heads, d = 40, 4, 32
    q, k, v, g, beta = _recurrence_inputs(n, heads, d, key=2)
    want = ref.delta_rule(q, k, v, g, beta)
    pool = jnp.zeros((2, 5, heads, d, d), F32).at[0].set(7.0)
    step = jax.jit(gdn_decode)
    for t in range(n):         # one sequence in slot 3 of layer 1, alone
        o, pool = step(pool, jnp.int32(1), jnp.asarray([3], jnp.int32),
                       q[t][None], k[t][None], v[t][None], g[t][None],
                       beta[t][None])
        assert compare.rel_l2(o[0], want[t]) < 1e-5, t
    assert float(jnp.min(pool[0])) == float(jnp.max(pool[0])) == 7.0
    assert float(jnp.max(jnp.abs(pool[1, jnp.asarray([0, 1, 2, 4])]))) == 0.0


@pytest.mark.parametrize("layer", [3, 7])
def test_full_layer_matches_reference(tiny, layer):
    """QK-norm, rotary on a quarter of each head, grouped causal attention,
    the sigmoid output gate."""
    c, a = tiny["model"].config, tiny["arch"]
    w = tiny["layer"](layer)
    pos = jnp.arange(T) + 5
    y = ref.rms_norm(_x(tiny), w["input_layernorm"], a.rms_norm_eps)
    want = ref.full_mixer(a, "float32", y, w, pos)
    ap = jax.tree.map(lambda t: t[layer // 4], tiny["serving"]["attn"])
    q, k, v, gate = hybrid.attn_project(c, ap, y, pos)
    group = c.num_heads // c.kv_heads
    s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, c.kv_heads, group, -1), k)
    s = jnp.where(jnp.arange(T)[:, None] >= jnp.arange(T)[None, :],
                  s / np.sqrt(c.head_dim), -1e30)
    att = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
    got = hybrid.attn_output(ap, att.reshape(q.shape), gate)
    assert compare.rel_l2(got, want) < 1e-5


@pytest.mark.parametrize("layer", [0, 3])
def test_expert_block_matches_reference(tiny, layer):
    c, a = tiny["model"].config, tiny["arch"]
    w = tiny["layer"](layer)
    x = _x(tiny, key=3)
    y = ref.rms_norm(x, w["post_attention_layernorm"], a.rms_norm_eps)
    want = ref.expert_block(a, "float32", y, w)
    lp = jax.tree.map(lambda t: t[layer], tiny["serving"]["layers"])
    got, counts = hybrid.expert_block(c, lp, tiny["serving"]["experts"], x,
                                      layer)
    assert compare.rel_l2(got - x, want) < 1e-5
    assert 0 < int(counts["experts_hit"]) <= a.num_experts
    assert 0 < int(counts["pairs"]) < T * a.num_experts_per_tok


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """Every chip's part of an expert layer, the shared expert counted once,
    is the whole layer: the reference told it holds all 16 experts against
    four program shares of 4, each given its own slice of the same
    weights."""
    a = tiny["arch"]
    whole = dataclasses.replace(a, num_experts=a.router_outputs,
                                expert_offset=0)
    w = weights.reference_layer_fn(whole, SEED, F32)(1)
    y = _x(tiny, key=4)
    want = ref.expert_block(whole, "float32", y, w)
    gate_cfg = tiny["model"].config.gate
    shared = {"wg": w["shared_gate_proj"], "wi": w["shared_up_proj"],
              "wo": w["shared_down_proj"], "gate": w["shared_expert_gate"]}

    def share(offset, valid=None):
        held = slice(offset, offset + a.num_experts)
        experts = {"wg": w["experts_gate_proj"][held],
                   "wi": w["experts_up_proj"][held],
                   "wo": w["experts_down_proj"][held]}
        return moe_ffn_share(y, w["gate"], experts, gate_cfg, offset=offset,
                             shared=shared, valid=valid)

    parts = [share(o) for o in range(0, a.router_outputs, a.num_experts)]
    # a token none of whose experts live here gets the shared expert alone
    alone, none = share(0, valid=jnp.zeros((y.shape[0],), bool))
    assert int(none["pairs"]) == int(none["experts_hit"]) == 0
    total = sum(out for out, _ in parts) - (len(parts) - 1) * alone
    assert compare.rel_l2(total, want) < 1e-5
    assert sum(int(c["pairs"]) for _, c in parts) \
        == y.shape[0] * a.num_experts_per_tok


def test_full_forward_matches_reference(tiny):
    toks = np.random.default_rng(0).integers(0, tiny["arch"].vocab_size, T)
    got = tiny["model"].apply(tiny["params"], jnp.asarray(toks[None]))[0]
    want = ref.forward_logits(tiny["arch"], [toks], [np.arange(T)],
                              tiny["layer"], tiny["top"])[0]
    assert compare.rel_l2(got, want) < 1e-4


# ---------------------------------------------------------------------------
# through the engine: put -> serve_step, both pools
# ---------------------------------------------------------------------------

def _engine(tiny, **kw):
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    args = dict(kv_blocks=64, kv_block_size=16, max_tokens_per_step=64,
                max_seqs_per_step=4, max_blocks_per_seq=8, state_slots=5)
    args.update(kw)
    return InferenceEngineV2(tiny["model"], mesh=mesh, params=tiny["params"],
                             dtype=F32, **args)


def _prompts(tiny, lens, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, tiny["arch"].vocab_size, n).astype(np.int32)
            for n in lens]


def _reference_rows(tiny, prompt, tokens):
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    rows = len(prompt) - 1 + np.arange(len(tokens))
    return np.asarray(ref.forward_logits(tiny["arch"], [seq], [rows],
                                         tiny["layer"], tiny["top"])[0])


def test_prefill_then_decode_through_both_pools_matches_reference(tiny):
    """Three requests together (prompts longer and shorter than a step's
    budget, so chunks of one prompt land in several steps): the first
    tokens from single steps, then bursts of 8, then single steps again;
    every logits row the engine sampled from against the reference's full
    forward. The rows after the bursts depend on every state the bursts
    left in both pools."""
    from benchmarks.generators.requests import Request, Served
    from benchmarks.runners.serve import LogitsTap

    eng = _engine(tiny)
    served = Served(eng)
    prompts = _prompts(tiny, (70, 23, 9))
    reqs = [Request(i + 1, p, 30) for i, p in enumerate(prompts)]
    rows = {r.rid: {} for r in reqs}
    eng.decode_steps = 1
    with LogitsTap(eng) as tap:
        for r in reqs:
            served.put(r)
        while served.outstanding:
            done = min(served.got.values())
            eng.decode_steps = 8 if 4 <= done < 20 else 1
            seen = len(tap.rows)
            before = dict(served.got)
            out = served.step()
            if len(tap.rows) == seen:
                continue                      # a burst: ids only
            for rid, toks in out.items():
                if len(toks) == 1 and rid in tap.slots[-1]:
                    rows[rid][before[rid]] = \
                        tap.rows[-1][tap.slots[-1].index(rid)]
    assert eng.stats["burst_steps"] >= 2 and eng.stats["tokens_decode"] > 0
    for r in reqs:
        want = _reference_rows(tiny, r.prompt, served.tokens[r.rid])
        assert max(rows[r.rid]) >= 25 and min(rows[r.rid]) == 0
        for j, row in rows[r.rid].items():
            assert compare.rel_l2(row, want[j]) < 2e-4, (r.rid, j)
        assert [int(np.argmax(w)) for w in want] == served.tokens[r.rid]
    pool = eng.kv_cache.state_pool
    assert pool.free_slots == pool.total_slots        # all given back
    ratio = eng.stats["moe_local_pairs"] / eng.stats["moe_token_layers"]
    assert 0.8 < ratio < 1.2          # 4 chosen of 16, 4 held: one a token
    eng.close()


def test_the_prefill_program_matches_reference_on_logits(tiny):
    """A lone prompt goes through the segment (Pallas prefill) program, a
    chunk a step, and not through the gather program: its logits too
    against the reference's full forward."""
    from benchmarks.generators.requests import Request, Served
    from benchmarks.runners.serve import LogitsTap

    eng = _engine(tiny, decode_steps=1, max_tokens_per_step=32)
    served = Served(eng)
    req = Request(1, _prompts(tiny, (75,), seed=8)[0], 6)
    rows = {}
    with LogitsTap(eng) as tap:
        served.put(req)
        while served.outstanding:
            before = served.got[1]
            if served.step().get(1):
                rows[before] = tap.rows[-1][tap.slots[-1].index(1)]
    assert eng.stats["prefill_kernel_steps"] == 3          # 32 + 32 + 11
    assert eng.stats["prefill_gather_fallbacks"] == 0
    want = _reference_rows(tiny, req.prompt, served.tokens[1])
    assert sorted(rows) == list(range(6))
    for j, row in rows.items():
        assert compare.rel_l2(row, want[j]) < 2e-4, j
    eng.close()


def _serve(eng, uids, prompts, new=14):
    eng.put(list(uids), list(prompts), max_new_tokens=new)
    return eng.generate_all()


def test_sequences_that_swap_slots_and_a_reused_slot_give_the_same_tokens(tiny):
    a, b = _prompts(tiny, (33, 41), seed=6)
    first = _engine(tiny)
    one = _serve(first, (1, 2), (a, b))                # a: slot 0, b: slot 1
    assert [first.state.seqs.get(u) for u in (1, 2)] == [None, None]
    second = _engine(tiny)
    two = _serve(second, (2, 1), (b, a))               # swapped
    assert one == two
    # a slot that held a finished sequence starts from zero for the next:
    # b alone, in the slot a just left, reads as b did beside a
    third = _engine(tiny, state_slots=1)
    assert _serve(third, (1,), (a,))[1] == one[1]
    assert _serve(third, (2,), (b,))[2] == one[2]
    for e in (first, second, third):
        e.close()
