"""The ninth architecture (``benchmarks/references/nemotron_h.py``,
``deepspeed_tpu/models/hybrid.py`` with blocks of one mixer each: Mamba-2,
squared-ReLU experts, attention without positions) through the unedited
training runner on the CPU at a toy size: a fixture manifest, configuration
and published file of its own (blocks 1-5 of 9, ``EMEM*``, 4 of 16 experts,
chunks of 16 under sequences of 64), judged ``correct`` against the
reference, and not ``correct`` against a reference with the gate after the
norm, ungrouped norm statistics, a SwiGLU for the squared ReLU or a rotated
attention, nor is the fp8 control; the committed configuration against its
published file and the catalog's arithmetic; the program's forward, loss and
gradients against the reference; the shares of an expert block adding up to
the uncut block; the new kernels' arithmetic and the new readers on a run
with nothing to read."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.harness import manifest as mf

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FX = os.path.join(HERE, "fixtures")
MANIFEST = os.path.join(FX, "BENCHMARK.tiny-nemotron.json")
CELL = "train-nemotron-ep16share-8k"
ENV = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


def _run(workload, manifest=MANIFEST):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 23), "--seconds", "1",
         "--trace", "0", "--manifest", manifest, "--rehearse"],
        env=ENV, capture_output=True, text=True, timeout=1200, cwd=ROOT)


def _config(path, name):
    with open(path) as f:
        man = json.load(f)
    here = os.path.dirname(path)
    bench_dir = os.path.normpath(os.path.join(here, man.get("bench_dir",
                                                            "benchmarks")))
    rel = next(c["file"] for c in man["configs"] if c["name"] == name)
    with open(os.path.join(here, rel)) as f:
        return json.load(f), bench_dir


def _numbers(out):
    lines = out.stdout.strip().splitlines()
    note = next(json.loads(l)["note"] for l in lines if '"numbers"' in l)
    return json.loads(lines[-1]), note["numbers"]


# the program as it is, and the program as it is against a reference that
# changes one mechanism (``fixtures/references/``): the two disagree as a
# program that changed it would disagree with the reference
@pytest.mark.parametrize("reference,correct", [
    ("nemotron_h", True), ("nemotron_gate_after_norm", False),
    ("nemotron_ungrouped_norm", False), ("nemotron_swiglu", False),
    ("nemotron_rope", False)])
def test_nemotron_cell_rehearses_and_tells_a_changed_mechanism(
        tmp_path, reference, correct):
    cfg, _ = _config(MANIFEST, "tiny-nemotron-train-c1")
    judged = dict(cfg, name="tiny-nemotron-judged", reference=reference)
    (tmp_path / "judged.json").write_text(json.dumps(judged))
    with open(MANIFEST) as f:
        man = json.load(f)
    man["bench_dir"] = FX
    man["configs"] = [{"name": "tiny-nemotron-judged", "file": "judged.json"}]
    man["workloads"] = [dict(man["workloads"][0], config="tiny-nemotron-judged")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out = _run("tiny-nemotron-train", str(tmp_path / "BENCHMARK.json"))
    assert out.returncode == 0, out.stderr[-3000:]
    last, numbers = _numbers(out)
    assert last["correct"] is correct and last["failed"] == 0
    assert set(last["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    if correct:
        # float32 fixture: the program is the reference's equations
        assert numbers["grad_leaves"] < 1e-3 and numbers["loss"] < 1e-5
        assert numbers["grad_norm"] < 1e-5
    else:
        assert numbers["grad_leaves"] > 0.1


def test_the_fp8_control_is_not_correct_at_the_fixture_s_limit():
    """The reference computed in the precision below the stated one, put in
    the program's place: it must fail the cell's own limit."""
    import numpy as np

    from benchmarks.harness import compare

    cfg, bench_dir = _config(MANIFEST, "tiny-nemotron-train-c1")
    runner = mf.load_module("runners", "train", bench_dir)
    ref_mod = mf.reference_of(cfg, bench_dir)
    arch = ref_mod.Arch.from_model(cfg)
    batch = np.random.default_rng(3).integers(0, 256, (1, 65)).astype(np.int32)
    ref = runner.reference_numbers(ref_mod, arch, cfg, batch, 11)
    low = runner.reference_numbers(ref_mod, arch, cfg, batch, 11, "fp8")
    worst = max(compare.rel_l2(low["kept"][k], ref["kept"][k])
                for k in ref["plan"])
    assert worst > 2 * cfg["check"]["limits"]["grad_leaves"]
    # every kind of leaf is in the plan, each top leaf whole
    names = {k.split(".")[-1] for k in ref["plan"]}
    assert names == set(ref_mod.CHECK_TOP_LEAVES) | {"block_norm"}
    assert ref["kept"]["mamba_in_proj"].shape == (2, 64, 196)
    assert ref["kept"]["experts_up_proj"].shape == (2, 4, 64, 32)


@pytest.mark.parametrize("path,name", [
    (os.path.join(mf.ROOT, "BENCHMARK.json"), "nemotron3-nano-train-c1"),
    (MANIFEST, "tiny-nemotron-train-c1")], ids=["committed", "fixture"])
def test_nemotron_configuration_cuts_depth_experts_and_vocabulary_alone(path, name):
    """Against its published file: ``reduced`` is exactly what differs; the
    router keeps its published outputs, the pattern stays whole; the preset
    the engine builds has the reference's sizes and kinds; the reference's
    leaf table is the program's tree, with no leaf for a part a block
    lacks."""
    import jax

    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(path, name)
    pub = mf.published_of(cfg, bench_dir)
    changed = sorted(k for k, v in pub["config"].items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"])
    assert set(changed) <= {"num_hidden_layers", "n_routed_experts",
                            "vocab_size"}
    assert cfg["router_outputs"] == pub["config"]["n_routed_experts"]
    assert pub["experts_key"] == "n_routed_experts"
    ref = mf.reference_of(cfg, bench_dir)
    arch = ref.Arch.from_model(cfg)
    held = cfg["num_hidden_layers"]
    model = get_model(cfg["preset"], num_layers=held, max_seq_len=64,
                      **cfg["preset_overrides"])
    c = model.config
    assert c._held_pattern == arch.kinds
    assert c.layer_pattern == pub["config"]["hybrid_override_pattern"]
    assert (c.recurrent_layers, c.expert_layers, c.kv_layers) == tuple(
        arch.blocks_of(k) for k in "ME*")
    assert (c.num_experts, c.held, c.top_k, c.routed_scale, c.router_scoring,
            c.shared_gate, c.partial_rotary_factor, c.qk_norm,
            c.attn_output_gate, c.activation, c.recurrent_kind) == (
        arch.router_outputs, arch.n_routed_experts, arch.num_experts_per_tok,
        arch.routed_scaling_factor, "sigmoid", False, 0.0, False, False,
        "relu2", "mamba2")
    assert (c.hidden_size, c.num_heads, c.kv_heads, c.head_dim,
            c.moe_ffn_size, c.shared_ffn_size, c.norm_eps, c.vocab_size,
            c.mamba_num_heads, c.mamba_head_dim, c.mamba_n_groups,
            c.mamba_state_size, c.mamba_conv_kernel, c.mamba_chunk) == (
        arch.hidden_size, arch.num_attention_heads, arch.num_key_value_heads,
        arch.head_dim, arch.moe_intermediate_size,
        arch.moe_shared_expert_intermediate_size, arch.layer_norm_epsilon,
        arch.vocab_size, arch.mamba_num_heads, arch.mamba_head_dim,
        arch.n_groups, arch.ssm_state_size, arch.conv_kernel, arch.chunk_size)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(k.key) for k in p): leaf.shape for p, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    table = {("layers/" if l.per_layer else "") + l.path.replace(".", "/"):
             ((held,) if l.per_layer else ()) + tuple(l.shape)
             for l in arch.leaf_table()}
    assert flat == table
    assert model.num_params() == sum(
        int(__import__("math").prod(s)) for s in table.values())
    if name == "nemotron3-nano-train-c1":
        assert arch.kinds == "EMEMEMEM*" and cfg["first_layer"] == 34
        for key in ("first_layer", "position_encoding", "block", "mamba_inner",
                    "gated_norm", "time_step_limit", "router",
                    "e_score_correction_bias", "loss", "mtp", "weights",
                    "row_buffer"):
            assert key in cfg["assumed"], key
        assert "ep = 16" in cfg["deployment"] and "sizing" in cfg
        assert cfg["published_counts"] == {
            k: pub["config"][k] for k in cfg["reduced"]}
        assert (cfg["seq_len"], cfg["vocab_size"], cfg["n_routed_experts"],
                cfg["job"]["train_micro_batch_size_per_chip"]) == (
            8192, 16384, 8, 2)
        assert pub["layer_period"] == 9
        assert model.num_params() == 666_963_456        # ISSUE 57: 667.0 M
        assert cfg["check"]["control"] == "fp8"
        assert "limits_from" in cfg["check"]


def test_the_committed_cell_is_listed_as_the_issue_names_it():
    man = mf.load_manifest()
    cell = mf.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron3-nano-train-c1", "train-stream-8k", 1)
    e2e = {m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s_chip", "setup_s"}
    layer = {m["name"] for m in mf.metrics_of(man, "per_layer", CELL)}
    assert layer >= {"mamba_train_ms", "ssd_chunk_ms",
                     "ssd_chunk_train_roofline", "moe_ungated_train_roofline",
                     "train_mfu", "flash_roofline", "moe_train_ms",
                     "moe_route_ms", "moe_pairs_per_token_layer"}
    # a SwiGLU's count would read these experts' floor half again too high
    assert "moe_grouped_train_roofline" not in layer
    assert "flash_window_roofline" not in layer
    new = [m for m in man["per_layer"] if m["name"] in (
        "mamba_train_ms", "ssd_chunk_ms", "ssd_chunk_train_roofline",
        "moe_ungated_train_roofline")]
    assert all(m["workloads"] == [CELL] and m["moves"]
               == "train_tokens_per_s_chip" for m in new) and len(new) == 4


def _seeded(dtype="float32"):
    import jax.numpy as jnp

    from benchmarks.harness import weights
    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(MANIFEST, "tiny-nemotron-train-c1")
    ref = mf.reference_of(cfg, bench_dir)
    arch = ref.Arch.from_model(cfg)
    model = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                      max_seq_len=64, **dict(cfg["preset_overrides"],
                                             dtype=dtype))
    seed = 7
    return (ref, arch, model, weights.make_program_params(arch, seed, jnp.float32),
            weights.reference_layer_fn(arch, seed, jnp.float32),
            weights.reference_top(arch, seed, jnp.float32))


def test_reference_agrees_with_the_program_forward_loss_and_gradients():
    """Two implementations that share no line (the reference runs the
    recurrence a token at a time, loops over the experts and masks keys in
    query blocks; the program runs the chunked scan, sorts rows by expert
    into a bounded buffer with the grouped product's own backward, and a
    checkpoint a block), float32, seeded weights: logits to 1e-4, the loss
    to 1e-6, every gradient leaf to 1e-3 (float32 sums over 64 tokens in
    another order; the scan's decays span e^-40 to 1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import compare, weights

    ref, arch, model, params, layer_fn, top = _seeded()
    ids = np.random.default_rng(0).integers(0, 256, (2, 65)).astype(np.int32)
    got = model.apply(params, jnp.asarray(ids[:, :-1]))
    want = ref.forward_logits(arch, [ids[0, :-1], ids[1, :-1]],
                              [np.arange(64)] * 2, layer_fn, top)
    assert max(compare.rel_l2(got[i], want[i]) for i in range(2)) < 1e-4
    out = ref.loss_and_grads(arch, ids, layer_fn, top,
                             lambda n, g: np.asarray(g))
    (loss, aux), grads = jax.value_and_grad(
        lambda p: model.loss(p, {"input_ids": ids}), has_aux=True)(params)
    assert abs(float(loss) - out["loss"]) < 1e-6 * out["loss"]
    assert int(aux["counters"]["moe_dropped_pairs"]) == 0
    assert int(aux["counters"]["moe_token_layers"]) == 2 * 2 * 64
    seen = set()
    for name, want_g in out["kept"].items():
        g = grads
        for part in weights.program_leaf_name(arch, name).split("."):
            g = g[part]
        if name.startswith("layers."):
            g = g[int(name.split(".")[1])]
        seen.add(name.split(".")[-1])
        if name == "e_score_correction_bias":   # chooses, never weighs
            assert not np.any(want_g) and not np.any(np.asarray(g))
        else:
            assert np.any(want_g), name
            assert compare.rel_l2(g, want_g) < 1e-3, name
    # every leaf of the table has been compared
    assert seen == {leaf.published for leaf in arch.leaf_table()}


def test_the_shares_of_an_expert_block_add_up_to_the_uncut_block():
    """Over all four offsets, what each chip's four experts add, plus the
    shared expert counted once, is the uncut reference's expert block: the
    program's ``moe_ffn_share`` at each offset against the reference with
    all 16 experts held."""
    import dataclasses

    import jax

    from benchmarks.harness import compare
    from deepspeed_tpu.parallel.moe import moe_ffn_share

    ref, arch, model, _, _, _ = _seeded()
    whole = dataclasses.replace(arch, n_routed_experts=16, expert_offset=0)
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    h, f, fs, R = (arch.hidden_size, arch.moe_intermediate_size,
                   arch.moe_shared_expert_intermediate_size, 16)
    y = jax.random.normal(ks[0], (96, h))
    w = {"router": jax.random.normal(ks[1], (h, R)) / h ** 0.5,
         "e_score_correction_bias": jax.random.normal(ks[2], (R,)) * 0.01,
         "shared_up_proj": jax.random.normal(ks[3], (h, fs)) / h ** 0.5,
         "shared_down_proj": jax.random.normal(ks[4], (fs, h)) / fs ** 0.5,
         "experts_up_proj": jax.random.normal(ks[5], (R, h, f)) / h ** 0.5,
         "experts_down_proj": jax.random.normal(ks[6], (R, f, h)) / f ** 0.5}
    want = ref.expert_mixer(whole, "float32", y, w)
    shared = ref.ffn(whole, "float32", y, w["shared_up_proj"],
                     w["shared_down_proj"])
    total, pairs = shared, 0
    for offset in range(0, R, 4):
        out, counts = moe_ffn_share(
            y, w["router"],
            {"wi": w["experts_up_proj"][offset:offset + 4],
             "wo": w["experts_down_proj"][offset:offset + 4]},
            model.config.gate, offset=offset,
            router_bias=w["e_score_correction_bias"],
            glu=model.config.expert_activation)
        total, pairs = total + out, pairs + int(counts["pairs"])
    assert pairs == 96 * arch.num_experts_per_tok
    assert compare.rel_l2(total, want) < 1e-5
    assert compare.rel_l2(total - shared, want - shared) < 1e-5
    # and the program's shared expert is the reference's, ungated
    out, _ = moe_ffn_share(
        y, w["router"], {"wi": w["experts_up_proj"][:4],
                         "wo": w["experts_down_proj"][:4]},
        model.config.gate, offset=0, router_bias=w["e_score_correction_bias"],
        shared={"wi": w["shared_up_proj"], "wo": w["shared_down_proj"]},
        glu="relu2")
    part = dataclasses.replace(arch, n_routed_experts=4, expert_offset=0)
    assert compare.rel_l2(out, ref.expert_mixer(part, "float32", y, w)) < 1e-5


def test_the_reference_s_recurrence_is_the_program_s_plain_one():
    """The reference's token-by-token scan in checkpointed segments against
    the program's own plain recurrence (``ssd_recurrence``): two writings of
    the same equations, values and a gradient; the segment length changes
    nothing."""
    import jax
    import jax.numpy as jnp

    from benchmarks.references import nemotron_h as ref
    from deepspeed_tpu.ops.pallas.mamba2 import ssd_recurrence

    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    T = 2 * ref.SEGMENT
    x = jax.random.normal(ks[0], (T, 4, 8))
    dt = jax.nn.softplus(2 * jax.random.normal(ks[1], (T, 4)))
    A = -jnp.exp(jax.random.normal(ks[2], (4,)))
    B, C = (jax.random.normal(k, (T, 2, 16)) for k in ks[3:5])
    D = jax.random.normal(ks[5], (4,))
    got = ref.recurrence("float32", x, dt, A, B, C, D)
    want = ssd_recurrence(x[None], dt[None], A, B[None], C[None], D)[0][0]
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())
    odd = ref.recurrence("float32", x[:T - 3], dt[:T - 3], A, B[:T - 3],
                         C[:T - 3], D)
    assert float(jnp.abs(odd - got[:T - 3]).max()) < 1e-5
    g1 = jax.grad(lambda a: jnp.sum(ref.recurrence("float32", x, dt, a, B, C,
                                                   D) ** 2))(A)
    g2 = jax.grad(lambda a: jnp.sum(ssd_recurrence(
        x[None], dt[None], a, B[None], C[None], D)[0] ** 2))(A)
    assert float(jnp.linalg.norm(g1 - g2) / jnp.linalg.norm(g2)) < 1e-4


def test_the_kernels_arithmetic_on_hand_counted_cases():
    from benchmarks.kernels import (moe_grouped_train, moe_ungated_train,
                                    ssd_chunk_train)

    # 10 pairs to 2 experts of 4 x 3: six products a pair, two matrices
    ops, nbytes = moe_ungated_train.step_calls(10, 2, 4, 3)
    assert ops == 6 * 2 * 4 * 3 * 10
    assert nbytes == 2 * (6 * 4 * 3 * 2 + 10 * 6 * (4 + 3))
    # two thirds of a SwiGLU expert's, operations and bytes alike
    gated = moe_grouped_train.step_calls(10, 2, 4, 3)
    assert (ops * 3, nbytes * 3) == (gated[0] * 2, gated[1] * 2)
    ev = '%grouped_matmul_dw.3 = bf16[8,64,32]{2,1,0} custom-call(...), ' \
         'custom_call_target="tpu_custom_call"'
    assert moe_ungated_train.classify(ev) == "dw"
    assert moe_ungated_train.classify(
        ev.replace("grouped_matmul_dw", "grouped_matmul")) == "gmm"
    assert moe_ungated_train.classify(ev.replace("grouped", "flash")) is None
    # one token, 2 heads of 4, 1 group, state 8, chunks of 3: (3 + 1) / 2
    # earlier tokens a token within the chunk
    ops, nbytes = ssd_chunk_train.step_calls(1, 2, 4, 1, 8, 3)
    macs = 2 * (1 * 8 + 2 * 4) + 2 * 2 * 4 * 8
    assert ops == 3 * 2 * macs
    operands = (8 + 16) * 2 + 2 * 4
    states = 2 * 4 * 8 * 4 / 3
    assert nbytes == pytest.approx(
        (operands + 32 + 2 * states) + (operands + 32 + states)
        + (operands + 2 * states))
    # the cell's scan: bound by bytes, some 150 kB a token and block
    ops, nbytes = ssd_chunk_train.step_calls(1, 64, 64, 8, 128, 128)
    assert 8.0e6 < ops < 8.6e6 and 1.4e5 < nbytes < 1.6e5
    assert nbytes / 819e9 > 4 * ops / 197e12
    # by scope path, with JAX's wrappers taken off
    fwd = "jit(dstpu_train_step)/jvp(mamba2)/ssd_chunk/dot_general"
    assert ssd_chunk_train.classify(fwd) == "fwd"
    assert ssd_chunk_train.classify(
        "jit(x)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
        "mamba2/ssd_chunk/exp") == "recompute"
    assert ssd_chunk_train.classify(
        "jit(x)/transpose(jvp(jvp()))/checkpoint/mamba2/ssd_chunk/mul") == "bwd"
    assert ssd_chunk_train.classify("jit(x)/jvp(mamba2)/mamba2_conv/add") is None
    assert ssd_chunk_train.classify("") is None


def test_train_flops_per_token_counts_this_share():
    cfg, bench_dir = _config(os.path.join(mf.ROOT, "BENCHMARK.json"),
                             "nemotron3-nano-train-c1")
    ref = mf.reference_of(cfg, bench_dir)
    a = ref.Arch.from_model(cfg)
    h = 2688
    mamba = (h * 10304 + 4096 * h + 4 * 6144
             + 64.5 * (8 * 128 + 64 * 64) + 2 * 64 * 64 * 128)
    experts = h * 128 + 2 * h * 3712 + 0.375 * 2 * h * 1856
    attn = h * 128 * (2 * 32 + 2 * 2) + 2 * 4096.5 * 128 * 32
    want = 6.0 * (4 * mamba + 4 * experts + attn + h * 16384)
    assert ref.train_flops_per_token(a, 8192) == want
    # ISSUE 57's arithmetic: the Mamba-2 blocks are half of the blocks' work
    blocks = 4 * mamba + 4 * experts + attn
    assert 0.45 < 4 * mamba / blocks < 0.6
    assert 2.0e9 < want < 2.3e9


@pytest.mark.parametrize("metric", [
    "mamba_train_ms", "ssd_chunk_ms", "ssd_chunk_train_roofline",
    "moe_ungated_train_roofline"])
def test_new_readers_read_nothing_where_there_is_nothing(metric):
    """A CPU rehearsal, an untraced run, or a program without the scope or
    the counters (a parent commit, another architecture): None, never an
    exception."""
    from deepspeed_tpu.observability.hub import reset_hub

    reset_hub()
    arch = types.SimpleNamespace(
        hidden_size=8, moe_intermediate_size=4, num_experts=4, expert_layers=4,
        num_attention_heads=2, num_key_value_heads=1, head_dim=4)
    ctx = types.SimpleNamespace(device={"platform": "cpu", "kind": "cpu"},
                                trace_dir="/nonexistent", note=lambda o: None)
    result = {"trace": None, "facts": {"arch": arch, "traced_steps": 0,
                                       "micro_per_chip": 1, "seq": 8}}
    assert mf.load_module("layer_metrics", metric).read(ctx, result) is None
