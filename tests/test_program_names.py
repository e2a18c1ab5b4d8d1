"""Names on the device: every jitted program lowers to a ``jit_dstpu_*``
module, every ``pallas_call`` carries its kernel name, and the train step
and the serving programs hold their ``jax.named_scope`` regions. Metadata
only: nothing here runs a program (docs/observability.md, "Profiler spans
and names")."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.inference import engine_v2
from deepspeed_tpu.inference.hybrid_runner import COUNTERS
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.ops.pallas import (blocksparse_attention, flash_attention,
                                      grouped_matmul, paged_attention,
                                      quantization)

F32, I32 = jnp.float32, jnp.int32


def _sds(shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _module(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


# -- serving programs --------------------------------------------------------

T, S, BM, NB, BS = 16, 4, 8, 32, 8      # tokens, slots, pages/seq, pool, page


def _serve_programs(preset):
    """Each serving program of the dense runner lowered on abstract
    arguments of a tiny model (a pool of ``ut_steps * num_layers`` slots)."""
    model = get_model(preset)
    cfg = model.config
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    kv = {"kv": _sds((cfg.ut_steps * cfg.num_layers, NB, BS, 2, cfg.kv_heads,
                      cfg.head_dim))}
    fns = engine_v2._shared_step_fns(cfg, None)
    ids = lambda *shape: _sds(shape, I32)  # noqa: E731
    return {
        "gather": fns["step"].lower(params, kv, ids(T), ids(T), ids(T),
                                    ids(S, BM), ids()),
        "prefill": fns["prefill"].lower(params, kv, ids(2, 8), ids(2),
                                        ids(2), ids(2, BM)),
        "decode": fns["decode"].lower(params, kv, ids(S), ids(S),
                                      ids(S, BM), ids(S)),
        "multi_decode": fns["multi_decode"].lower(
            params, kv, ids(S), ids(S), ids(S, BM), ids(S), steps=3),
    }


@pytest.fixture(scope="module")
def serve_lowered():
    return _serve_programs("tiny")


@pytest.fixture(scope="module")
def looped_lowered():
    return _serve_programs("tiny-ouro")


@pytest.mark.parametrize("program", ["gather", "prefill", "decode",
                                     "multi_decode"])
def test_serving_program_module_name(serve_lowered, program):
    assert _module(serve_lowered[program]) == f"jit_dstpu_serve_{program}"


@pytest.mark.parametrize("program,scopes", [
    ("gather", ["kv_write", "kv_gather", "attn", "mlp", "head"]),
    ("prefill", ["kv_write", "attn/kv_gather", "attn", "mlp", "head"]),
    ("decode", ["kv_write", "attn", "mlp", "head"]),
    ("multi_decode", ["kv_write", "attn", "mlp", "head"]),
])
def test_serving_program_scopes(serve_lowered, program, scopes):
    text = serve_lowered[program].as_text(debug_info=True)
    for scope in scopes:
        assert re.search(rf'"[^"]*\b{scope}/[^"]*"', text), (program, scope)
    # the [T, max_ctx, ...] gather is the gather program's, a sequence's
    # pages read once a chunk the prefill program's; the kernels read pages
    if program not in ("gather", "prefill"):
        assert "kv_gather/" not in text


@pytest.mark.parametrize("program", ["gather", "prefill", "decode",
                                     "multi_decode"])
def test_looped_programs_hold_the_loops_scopes(looped_lowered, program):
    """A looped stack (``tiny-ouro``: three layers, four passes, a pool of
    twelve slots) in the dense runner's four programs: ``loop_pass_ms`` keys
    on ``ut_pass``, under which the layer's own scopes lie; the norm between
    passes is ``pass_norm``; the exit gate is in no step program (threshold
    1: nothing reads it)."""
    lowered = looped_lowered[program]
    assert _module(lowered) == f"jit_dstpu_serve_{program}"
    text = lowered.as_text(debug_info=True)
    for scope in ("ut_pass/attn", "ut_pass/kv_write", "ut_pass/mlp",
                  "pass_norm", "head"):
        assert scope + "/" in text, scope
    assert "exit_gate" not in text


@pytest.mark.parametrize("fn,args,name", [
    (engine_v2._PICK_GREEDY, (_sds((T, 64)), _sds((S,), I32)),
     "jit_dstpu_pick_greedy"),
    (engine_v2._TAKE_ROWS, (_sds((T, 64)), _sds((S,), I32)),
     "jit_dstpu_take_rows"),
    (engine_v2._PICK_GREEDY_ALL, (_sds((T, 64)),),
     "jit_dstpu_pick_greedy_all"),
])
def test_token_pick_module_name(fn, args, name):
    assert _module(fn.lower(*args)) == name


def test_step_programs_are_shared_by_config():
    """The names did not cost the process-level sharing: a second engine
    over the same config object reuses the first one's jitted callables."""
    cfg = get_model("tiny").config
    assert engine_v2._shared_step_fns(cfg, None) is \
        engine_v2._shared_step_fns(cfg, None)


# -- the grouped product's tiles are chosen when a program is traced ---------

def _grouped_matmul_calls(jaxpr, under_cond=False):
    """(calls of the ``grouped_matmul`` kernel, those under a ``cond``) in a
    jaxpr and every jaxpr nested in it."""
    calls = conds = 0
    for eqn in jaxpr.eqns:
        inside = under_cond or eqn.primitive.name == "cond"
        if eqn.primitive.name == "pallas_call" and re.search(
                r"\bname=grouped_matmul\b", str(eqn)):
            calls, conds = calls + 1, conds + inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            c, k = _grouped_matmul_calls(sub, inside)
            calls, conds = calls + c, conds + k
    return calls, conds


@pytest.mark.parametrize("program", ["gather", "decode", "multi_decode",
                                     "prefill"])
def test_hybrid_programs_hold_one_grouped_product_a_call_site(program):
    """Three products an expert block, one block a run of one kind of layer
    (``stack_plan``): that many kernels in the program and no conditional
    around any. The tile is a function of the static shapes, so a program
    holds no variant of the kernel to choose from while it runs. The
    prefill program's chunk attention is plain products over each
    segment's own pages: no ``paged_prefill`` kernel in it."""
    model = get_model("tiny-hybrid")
    cfg = model.config
    params = jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    slots, taps = 5, cfg.linear_conv_kernel_dim - 1
    pools = {
        "kv": _sds((cfg.kv_layers, NB, BS, 2, cfg.kv_heads, cfg.head_dim)),
        "state": _sds((cfg.recurrent_layers, slots,
                       cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                       cfg.linear_value_head_dim)),
        "conv": _sds((cfg.recurrent_layers, slots, taps, cfg.conv_channels)),
        "counters": _sds((len(COUNTERS),), I32)}
    fns = engine_v2._shared_step_fns(cfg, None)
    ids = lambda *shape: _sds(shape, I32)  # noqa: E731
    if program == "gather":
        traced = fns["step"].trace(params, pools, ids(T), ids(T), ids(T),
                                   ids(S, BM), ids(), ids(S))
    elif program == "prefill":
        traced = fns["prefill"].trace(params, pools, ids(2, 64), ids(2),
                                      ids(2), ids(2, BM), ids(S))
        assert _module(traced.lower()) == "jit_dstpu_serve_prefill"
        kernels = set(re.findall(r"\bname=(\w+)", str(traced.jaxpr)))
        assert "paged_prefill" not in kernels and "grouped_matmul" in kernels
    else:
        steps = {"steps": 3} if program == "multi_decode" else {}
        traced = fns[program].trace(params, pools, ids(S), ids(S), ids(S, BM),
                                    ids(S), ids(S), **steps)
    calls, under_cond = _grouped_matmul_calls(traced.jaxpr.jaxpr)
    assert calls == 3 * len(cfg.stack_plan[1]) and under_cond == 0


def test_a_start_of_a_recurrent_model_builds_no_gather_program():
    """A model with recurrent layers that has a gather program
    (``tiny-hybrid``), through the benchmark's ``warm_up`` at a toy budget
    (64 tokens and 4 sequences a step, bursts of 1 and 2): every step is
    split by program, so the gather program is never traced, and the
    prefill program is built for the ``(S, tq)`` layouts twice the budget
    admits and no other (chunks bucket from the recurrence's 64)."""
    from benchmarks.generators.requests import Served
    from benchmarks.runners import serve

    model = get_model("tiny-hybrid", param_dtype=F32, dtype=F32)
    budget = {"kv_blocks": 64, "kv_block_size": 16, "max_tokens_per_step": 64,
              "max_seqs_per_step": 4, "max_blocks_per_seq": 8,
              "state_slots": 8}
    eng = engine_v2.InferenceEngineV2(
        model, params=model.init(jax.random.PRNGKey(0)), dtype=F32, **budget)
    dispatch, shapes = eng._dispatch, set()

    def recorded(program, *args, **shape):
        if program == "prefill":
            shapes.add((shape["S"], shape["tq"]))
        return dispatch(program, *args, **shape)

    eng._dispatch = recorded
    serve.warm_up(Served(eng), {"engine": budget, "decode_steps": 2},
                  model.config.vocab_size)
    assert shapes == {(1, 64), (2, 64)}
    assert all(S * tq <= 2 * 64 for S, tq in shapes)
    assert eng._step_fn._cache_size() == 0          # jit_dstpu_serve_gather
    # (one entry more for the engine's first call, which takes the pools
    # as they were made: not yet committed to their device)
    assert eng._prefill_fn._cache_size() == len(shapes) + 1
    assert eng._decode_fn._cache_size() == 1
    st = eng.stats
    assert st["calls_gather"] == 0 == st["prefill_gather_fallbacks"]
    assert st["calls_prefill"] == st["prefill_chunk_calls"] > 0
    assert st["calls_multi_decode"] > 0
    eng.close()


@pytest.mark.parametrize("program,scopes", [
    ("decode", ["mla/mla_project", "mla/mla_absorb", "mla/mla_attn",
                "dense_ffn", "moe/moe_route", "moe/moe_shared"]),
    ("prefill", ["mla/mla_project", "mla/mla_chunk", "dense_ffn",
                 "moe/moe_experts"])])
def test_latent_programs_hold_their_scopes_and_one_layer_body(program, scopes):
    """The trace readers key on these paths (``mla_decode_ms`` on ``mla``,
    ``mla_decode_roofline`` on ``mla_attn``). The dense prologue is a layer
    of its own before the scan and the expert layers one scanned body: two
    ``mla_decode`` kernels and three grouped products a token step, whatever
    the depth."""
    model = get_model("tiny-kimi", num_layers=5)
    cfg = model.config
    params = jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pools = {"kv": _sds((cfg.kv_layers, NB, BS, 256)),
             "counters": _sds((len(COUNTERS),), I32)}
    fns = engine_v2._shared_step_fns(cfg, None)
    ids = lambda *shape: _sds(shape, I32)  # noqa: E731
    if program == "prefill":
        traced = fns["prefill"].trace(params, pools, ids(1, 16), ids(1),
                                      ids(1), ids(1, BM))
    else:
        traced = fns["decode"].trace(params, pools, ids(S), ids(S),
                                     ids(S, BM), ids(S))
    text = traced.lower().as_text(debug_info=True)
    for path in scopes:
        assert path in text, path
    assert _module(traced.lower()) == f"jit_dstpu_serve_{program}"
    if program == "decode":
        calls, under_cond = _grouped_matmul_calls(traced.jaxpr.jaxpr)
        assert calls == 3 and under_cond == 0
        assert len(re.findall(r"\bname=mla_decode\b",
                              str(traced.jaxpr))) == 2


@pytest.mark.parametrize("program,scopes", [
    ("decode", ["mla/mla_project", "mla/dsa_index", "mla/dsa_select",
                "mla/dsa_attn", "mla/mla_absorb", "mla/attn_gate",
                "wmla/mla_project", "wmla/wmla_attn", "wmla/attn_gate",
                "dense_ffn", "moe/moe_route"]),
    ("prefill", ["mla/mla_project", "mla/dsa_index", "mla/mla_chunk",
                 "wmla/wmla_chunk", "wmla/attn_gate", "dense_ffn",
                 "moe/moe_experts"])])
def test_selected_and_windowed_latent_programs_hold_their_scopes(program,
                                                                  scopes):
    """The trace readers key on these paths (``dsa_index_ms`` on
    ``dsa_index`` and ``dsa_select``, ``dsa_attn_ms`` on ``dsa_attn``,
    ``window_mla_decode_ms`` on ``wmla``, its roofline on ``wmla_attn``).
    The dense prologue (a full layer) runs before the scan; the two periods
    are one scanned body of a full layer and a scanned run of three sliding
    ones: in a token step two ``dsa_index`` kernels and three ``mla_decode``
    (two over chosen tokens, one over a ring), whatever the depth."""
    model = get_model("tiny-dots3")
    cfg = model.config
    params = jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    ring = 4
    pools = {"kv": _sds((cfg.kv_layers, NB, BS, 256)),
             "ik": _sds((cfg.kv_layers, NB, BS, cfg.index_key_dim)),
             "wkv": _sds((cfg.window_layers, S * ring + 1, BS, 256)),
             "counters": _sds((len(COUNTERS),), I32)}
    fns = engine_v2._shared_step_fns(cfg, None)
    ids = lambda *shape: _sds(shape, I32)  # noqa: E731
    if program == "prefill":
        traced = fns["prefill"].trace(params, pools, ids(1, 16), ids(1),
                                      ids(1), ids(1, BM),
                                      window_table=ids(S, ring))
    else:
        traced = fns["decode"].trace(params, pools, ids(S), ids(S),
                                     ids(S, BM), ids(S),
                                     window_table=ids(S, ring))
    text = traced.lower().as_text(debug_info=True)
    for path in scopes:
        assert path in text, path
    assert _module(traced.lower()) == f"jit_dstpu_serve_{program}"
    if program == "decode":
        jaxpr = str(traced.jaxpr)
        assert len(re.findall(r"\bname=dsa_index\b", jaxpr)) == 2
        assert len(re.findall(r"\bname=mla_decode\b", jaxpr)) == 3


@pytest.mark.parametrize("program,scopes", [
    ("decode", ["attn/msa_index", "attn/msa_index/msa_pool_write",
                "attn/msa_attn", "dense_ffn", "moe/moe_route",
                "moe/moe_shared"]),
    ("prefill", ["attn/msa_index", "attn/msa_index/msa_pool_write",
                 "attn/msa_attn", "dense_ffn", "moe/moe_experts"])])
def test_block_selecting_programs_hold_their_scopes(program, scopes):
    """The trace readers key on these paths (``msa_index_ms`` on
    ``msa_index``, the pooled keys' write inside it; ``msa_chunk_attn_ms``
    on ``msa_attn``; ``moe_chunk_ms`` on ``moe``). The dense layer runs
    before the scan and the expert layers are one scanned body: two paged
    decode kernels in a token step (over the pages each (sequence, KV head)
    chose) and two block-masked chunk kernels in a chunk call, each inside
    ``msa_attn``, whatever the depth, and no attention gate."""
    model = get_model("tiny-m3")
    cfg = model.config
    params = jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    bs = cfg.msa.block
    pools = {"kv": _sds((cfg.kv_layers, NB, bs, 2, cfg.kv_heads,
                         cfg.head_dim)),
             "pk": _sds((cfg.kv_layers, NB, cfg.kv_heads,
                         cfg.msa.index_dim)),
             "counters": _sds((len(COUNTERS),), I32)}
    fns = engine_v2._shared_step_fns(cfg, None)
    ids = lambda *shape: _sds(shape, I32)  # noqa: E731
    if program == "prefill":
        traced = fns["prefill"].trace(params, pools, ids(1, 16), ids(1),
                                      ids(1), ids(1, BM))
    else:
        traced = fns["decode"].trace(params, pools, ids(S), ids(S),
                                     ids(S, BM), ids(S))
    text = traced.lower().as_text(debug_info=True)
    for path in scopes:
        assert path in text, path
    assert "attn_gate" not in text
    assert _module(traced.lower()) == f"jit_dstpu_serve_{program}"
    jaxpr = str(traced.jaxpr)
    if program == "decode":
        assert len(re.findall(r"\bname=paged_decode\b", jaxpr)) == 2
    else:
        # one function of the program holds the chunk kernel, traced and
        # lowered once and called by the dense layer and the scanned ones;
        # its operations name the scope the trace readers sum themselves
        assert len(re.findall(r"\bname=_msa_chunk_attention\b", jaxpr)) == 2
        assert len(re.findall(r"\bname=paged_block_prefill\b", jaxpr)) == 1
        assert len(re.findall(r"func.func private @_msa_chunk_attention",
                              text)) == 1
        assert re.search(r'"msa_attn/(\w+/)*paged_block_prefill', text)
        assert "sparse_attn" not in text    # the plain form: the first rule's


# -- training programs -------------------------------------------------------

TINY = TransformerConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
    max_seq_len=32, pos_emb="learned", norm="layernorm",
    activation="gelu", tie_embeddings=True, remat=True)
BASE = {"train_micro_batch_size_per_chip": 2, "steps_per_print": 10**9,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}}}


def _engine(**extra):
    engine, *_ = dstpu.initialize(model=TransformerLM(TINY),
                                  config=dict(BASE, **extra))
    return engine


def _batches(engine):
    ids = np.zeros((1, engine.train_batch_size, 17), np.int32)
    return engine.shard_batch({"input_ids": ids}, leading_dims=2)


@pytest.fixture(scope="module")
def train_engine(devices):
    engine = _engine(zero_optimization={"stage": 3}, bf16={"enabled": True})
    yield engine
    engine.close()


def _train_lowered(engine, which):
    e, b = engine, _batches(engine)
    one = jax.tree.map(lambda x: x[0], b)
    scale = jnp.asarray(1.0, F32)
    if which == "train_step":
        return e._jit_train_step.lower(e.params, e.opt_state,
                                       e.loss_scale_state, e.step_count, b)
    if which == "grad_step":
        return e._jit_grad_step.lower(e.params, b, scale)
    if which == "fwd_bwd":
        return e._jit_fwd_bwd.lower(e.params, one, scale)
    if which == "eval":
        return e._jit_eval.lower(e.params, one)
    grads = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), e.params)
    return e._jit_apply.lower(e.params, e.opt_state, e.loss_scale_state,
                              e.step_count, grads, jnp.asarray(0.0))


@pytest.mark.parametrize("which", ["train_step", "grad_step", "fwd_bwd",
                                   "apply_update", "eval"])
def test_training_program_module_name(train_engine, which):
    assert _module(_train_lowered(train_engine, which)) == f"jit_dstpu_{which}"


def test_onebit_step_module_name(devices):
    engine = _engine(optimizer={"type": "onebitadam",
                                "params": {"lr": 1e-2, "freeze_step": 4}},
                     zero_optimization={"stage": 1})
    low = engine._jit_onebit.lower(engine.params, engine._onebit_state,
                                   _batches(engine), jnp.asarray(0.0, F32))
    assert _module(low) == "jit_dstpu_onebit_step"
    engine.close()


def test_zeropp_step_module_name(devices):
    engine = _engine(zero_optimization={"stage": 1,
                                        "zero_quantized_allreduce": True})
    low = engine._jit_zeropp.lower(engine.params, engine._zeropp_state,
                                   _batches(engine), jnp.asarray(0.0, F32))
    assert _module(low) == "jit_dstpu_zeropp_step"
    engine.close()


def test_train_step_scopes(train_engine):
    """``forward_backward`` holds the forward (``jvp``) and what JAX marks
    as transposed; ``optimizer`` the update; the model's regions are named
    inside the layer body."""
    text = _train_lowered(train_engine, "train_step").as_text(debug_info=True)
    for path in ["forward_backward/jvp(", "forward_backward/transpose(jvp(",
                 "optimizer/", "embed", "head_loss", "attn/", "mlp/"]:
        assert path in text, path
    # the compiled HLO composes the whole path, through the scan and the
    # rematerialised layer body, onto each op
    ops = set(re.findall(r'op_name="([^"]*)"',
                         _train_lowered(train_engine, "train_step")
                         .compile().as_text()))
    fwd = [o for o in ops if "/forward_backward/jvp(" in o and "/attn/" in o]
    bwd = [o for o in ops if "/forward_backward/transpose(jvp(" in o]
    assert fwd and bwd and any("/optimizer/" in o for o in ops)


# -- Pallas kernels ----------------------------------------------------------

def _kernel_names(fn, *args):
    return set(re.findall(r"\bname=(\w+)", str(jax.make_jaxpr(fn)(*args))))


def _flash(q, k, v):
    return jnp.sum(flash_attention.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128))


def _gmm(lhs, rhs, sizes):
    return jnp.sum(grouped_matmul.gmm(lhs, rhs, sizes))


_QKV = (_sds((1, 256, 4, 64)), _sds((1, 256, 2, 64)), _sds((1, 256, 2, 64)))
_POOL = _sds((16, 8, 2, 2, 64))

KERNELS = [
    ("flash_fwd", _flash, _QKV),
    ("flash_bwd_dkdv", jax.grad(_flash, argnums=(0, 1, 2)), _QKV),
    ("flash_bwd_dq", jax.grad(_flash, argnums=(0, 1, 2)), _QKV),
    ("paged_decode", paged_attention.paged_decode_attention,
     (_sds((4, 4, 64)), _POOL, _sds((4, 4), I32), _sds((4,), I32))),
    ("mla_decode",
     lambda q, kv, bt, ctx: paged_attention.mla_decode_attention(
         q, kv, bt, ctx, value_dim=128, scale=0.1, layer=1),
     (_sds((4, 4, 256)), _sds((2, 16, 8, 256)), _sds((4, 4), I32),
      _sds((4,), I32))),
    ("paged_prefill", paged_attention.paged_prefill_attention,
     (_sds((2, 8, 4, 64)), _POOL, _sds((2, 4), I32), _sds((2,), I32),
      _sds((2,), I32))),
    ("paged_block_prefill",
     lambda q, kv, bt, mask, pos0, ctx: paged_attention.block_prefill_attention(
         q, kv, bt, mask, pos0, ctx)[0],
     (_sds((2, 8, 4, 64)), _POOL, _sds((2, 4), I32),
      _sds((2, 8, 2, 4), jnp.bool_), _sds((2,), I32), _sds((2,), I32))),
    ("grouped_matmul", _gmm,
     (_sds((256, 128)), _sds((2, 128, 128)), _sds((2,), I32))),
    ("grouped_matmul_dw", jax.grad(_gmm, argnums=(0, 1)),
     (_sds((256, 128)), _sds((2, 128, 128)), _sds((2,), I32))),
    ("blocksparse_fwd",
     lambda q, k, v: blocksparse_attention.blocksparse_attention_pallas(
         q, k, v, blocksparse_attention.make_sparsity_config(
             "fixed", block=16), causal=True),
     (_sds((1, 64, 2, 16)),) * 3),
    ("quantize_blockwise", quantization.quantize_blockwise,
     (_sds((64, 256)),)),
]


@pytest.mark.parametrize("name,fn,args", KERNELS, ids=[k[0] for k in KERNELS])
def test_pallas_kernel_carries_its_name(monkeypatch, name, fn, args):
    # quantize_blockwise takes its reference path under the interpreter;
    # tracing (nothing runs) is enough to see the call
    monkeypatch.setattr(quantization, "_interpret", lambda: False)
    assert name in _kernel_names(fn, *args)


def test_every_pallas_call_site_passes_a_name():
    import glob
    import os

    root = os.path.dirname(flash_attention.__file__)
    for path in glob.glob(os.path.join(root, "*.py")):
        src = open(path).read()
        calls = len(re.findall(r"pl\.pallas_call\(", src))
        named = len(re.findall(r"pl\.pallas_call\([^#]*?\bname=\"\w+\"", src,
                               re.S))
        assert calls == named, (os.path.basename(path), calls, named)
