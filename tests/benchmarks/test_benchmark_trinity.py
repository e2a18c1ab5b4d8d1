"""The fifth architecture (``benchmarks/references/trinity.py``,
``deepspeed_tpu/models/hybrid.py`` with windowed layers, rotary by layer
kind, four norms a layer and a share of sigmoid-routed experts that trains)
through the unedited training runner on the CPU at a toy size: a fixture
manifest, configuration and published file of its own (layers 1-5 of 8, 4
of 16 experts, a window of 24 under sequences of 64), judged ``correct``
against the reference, and not ``correct`` against a reference with the
window dropped, the full layer rotated, the bias left out of the choice or
the experts' gradients gone; the committed configuration against its
published file; the program's forward, loss and gradients against the
reference; the shares of a layer adding up to the uncut layer; the new
kernels' arithmetic and the new readers on a run with nothing to read."""

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
MANIFEST = os.path.join(FX, "BENCHMARK.tiny-trinity.json")
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
# drops one mechanism (``fixtures/references/``): the two disagree as a
# program that dropped it would disagree with the reference
@pytest.mark.parametrize("reference,correct", [
    ("trinity", True), ("trinity_no_window", False),
    ("trinity_full_rope", False), ("trinity_no_bias", False),
    ("trinity_no_expert_grads", False)])
def test_trinity_cell_rehearses_and_tells_a_dropped_mechanism(
        tmp_path, reference, correct):
    cfg, _ = _config(MANIFEST, "tiny-trinity-train-c1")
    judged = dict(cfg, name="tiny-trinity-judged", reference=reference)
    (tmp_path / "judged.json").write_text(json.dumps(judged))
    with open(MANIFEST) as f:
        man = json.load(f)
    man["bench_dir"] = FX
    man["configs"] = [{"name": "tiny-trinity-judged", "file": "judged.json"}]
    man["workloads"] = [dict(man["workloads"][0], config="tiny-trinity-judged")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out = _run("tiny-trinity-train", str(tmp_path / "BENCHMARK.json"))
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


@pytest.mark.parametrize("path,name", [
    (os.path.join(mf.ROOT, "BENCHMARK.json"), "trinity-mini-train-c1"),
    (MANIFEST, "tiny-trinity-train-c1")], ids=["committed", "fixture"])
def test_trinity_configuration_cuts_depth_experts_and_vocabulary_alone(path, name):
    """Against its published file: ``reduced`` is exactly what differs; the
    router keeps its published outputs, ``layer_types`` and
    ``num_dense_layers`` stay whole; the preset the engine builds has the
    reference's sizes and kinds; the reference's leaf table is the program's
    tree, with no expert slot for a dense layer."""
    import jax

    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(path, name)
    pub = mf.published_of(cfg, bench_dir)
    changed = sorted(k for k, v in pub["config"].items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"])
    assert set(changed) <= {"num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["router_outputs"] == pub["config"]["num_experts"]
    assert pub["experts_key"] == "num_experts" and pub["layer_period"] == 4
    arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
    held = cfg["num_hidden_layers"]
    assert (arch.dense_layers, arch.expert_layers) == (1, 4)
    assert [arch.is_sliding(l) for l in range(held)] == [
        True, True, False, True, True]
    model = get_model(cfg["preset"], num_layers=held, max_seq_len=64,
                      **cfg["preset_overrides"])
    c = model.config
    assert c.layer_windows == tuple(arch.window_of(l) for l in range(held))
    assert (c.dense_layers, c.num_experts, c.held, c.top_k, c.routed_scale,
            c.router_scoring, c.shared_gate, c.post_norms,
            c.partial_rotary_factor, c.window_rotary_factor) == (
        arch.dense_layers, arch.router_outputs, arch.num_experts,
        arch.num_experts_per_tok, arch.route_scale, "sigmoid", False, True,
        0.0, 1.0)
    assert (c.hidden_size, c.num_heads, c.kv_heads, c.head_dim, c.ffn_size,
            c.moe_ffn_size, c.norm_eps, c.rope_theta, c.vocab_size) == (
        arch.hidden_size, arch.num_attention_heads, arch.num_key_value_heads,
        arch.head_dim, arch.intermediate_size, arch.moe_intermediate_size,
        arch.rms_norm_eps, arch.rope_theta, arch.vocab_size)
    assert abs(c.scale_emb - arch.hidden_size ** 0.5) < 1e-9
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(k.key) for k in p): leaf.shape for p, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    table = {("layers/" if l.per_layer else "") + l.path.replace(".", "/"):
             ((held,) if l.per_layer else ()) + tuple(l.shape)
             for l in arch.leaf_table()}
    assert flat == table
    assert flat["experts/wg"][0] == held - 1        # the expert layers alone
    if name == "trinity-mini-train-c1":
        for key in ("first_layer", "mup_enabled", "norms", "gate_proj",
                    "rotary", "router", "expert_bias", "weights",
                    "row_buffer"):
            assert key in cfg["assumed"], key
        assert "ep = 8" in cfg["deployment"] and "sizing" in cfg
        assert cfg["published_counts"] == {
            k: pub["config"][k] for k in cfg["reduced"]}
        assert (cfg["seq_len"], cfg["vocab_size"], cfg["num_experts"]) == (
            8192, 25024, 16)


def _seeded(dtype="float32"):
    import jax.numpy as jnp

    from benchmarks.harness import weights
    from deepspeed_tpu.models.zoo import get_model

    cfg, bench_dir = _config(MANIFEST, "tiny-trinity-train-c1")
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
    """Two implementations that share no line (the reference computes every
    held expert for every token and masks keys in query blocks; the program
    sorts rows by expert into a bounded buffer, runs the grouped product's
    own backward and a checkpoint a layer), float32, seeded weights: logits
    to 1e-4, the loss to 1e-6, every gradient leaf to 1e-3 (attention as
    peaked as the draw makes it carries float32's rounding further)."""
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
    assert int(aux["counters"]["moe_token_layers"]) == 4 * 2 * 64
    zero = set()
    for name, want_g in out["kept"].items():
        g = grads
        for part in weights.program_leaf_name(arch, name).split("."):
            g = g[part]
        if name.startswith("layers."):
            g = g[int(name.split(".")[1])]
        if not np.any(want_g):
            zero.add(name.split(".")[-1])
            assert not np.any(np.asarray(g)), name
        else:
            assert compare.rel_l2(g, want_g) < 1e-3, name
    # the bias chooses and never weighs; the dense layer's slots are dead
    assert zero == {"expert_bias", "router", "shared_gate_proj",
                    "shared_up_proj", "shared_down_proj"}
    assert np.any(out["kept"]["experts_up_proj"][2])


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Over all four offsets, what each chip's four experts add, plus the
    shared expert counted once, is the uncut reference's expert block: the
    program's ``moe_ffn_share`` at each offset against the reference with
    all 16 experts held."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import compare
    from deepspeed_tpu.parallel.moe import moe_ffn_share

    ref, arch, model, _, _, _ = _seeded()
    whole = dataclasses.replace(arch, num_experts=16, expert_offset=0)
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    h, f, R = arch.hidden_size, arch.moe_intermediate_size, 16
    y = jax.random.normal(ks[0], (96, h))
    w = {"router": jax.random.normal(ks[1], (h, R)) / h ** 0.5,
         "expert_bias": jax.random.normal(ks[2], (R,)) * 0.01,
         "shared_gate_proj": jax.random.normal(ks[3], (h, f)) / h ** 0.5,
         "shared_up_proj": jax.random.normal(ks[4], (h, f)) / h ** 0.5,
         "shared_down_proj": jax.random.normal(ks[5], (f, h)) / f ** 0.5}
    ew = {"experts_gate_proj": jax.random.normal(ks[6], (R, h, f)) / h ** 0.5,
          "experts_up_proj": jax.random.normal(ks[7], (R, h, f)) / h ** 0.5,
          "experts_down_proj": jax.random.normal(ks[0], (R, f, h)) / f ** 0.5}
    want = ref.expert_block(whole, "float32", y, w, ew)
    shared = ref.swiglu("float32", y, w["shared_gate_proj"],
                        w["shared_up_proj"], w["shared_down_proj"])
    total, pairs = shared, 0
    for offset in range(0, R, 4):
        out, counts = moe_ffn_share(
            y, w["router"],
            {"wg": ew["experts_gate_proj"][offset:offset + 4],
             "wi": ew["experts_up_proj"][offset:offset + 4],
             "wo": ew["experts_down_proj"][offset:offset + 4]},
            model.config.gate, offset=offset, router_bias=w["expert_bias"])
        total, pairs = total + out, pairs + int(counts["pairs"])
    assert pairs == 96 * arch.num_experts_per_tok
    assert compare.rel_l2(total, want) < 1e-5
    assert compare.rel_l2(total - shared, want - shared) < 1e-5


def test_a_row_buffer_too_small_drops_pairs_and_counts_them():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.parallel.moe import GateConfig, moe_ffn_share

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    y = jax.random.normal(ks[0], (256, 32))
    experts = {n: jax.random.normal(k, s) * 0.1 for n, k, s in (
        ("wg", ks[1], (4, 32, 16)), ("wi", ks[2], (4, 32, 16)),
        ("wo", ks[3], (4, 16, 32)))}
    router = jax.random.normal(ks[4], (32, 4))
    gate = GateConfig(num_experts=4, top_k=2, drop_tokens=False,
                      scoring="sigmoid")
    full, c_full = moe_ffn_share(y, router, experts, gate)
    out, c = moe_ffn_share(y, router, experts, gate, capacity=512)
    assert int(c["dropped"]) == 0 and jnp.allclose(out, full, atol=1e-5)
    out, c = moe_ffn_share(y, router, experts, gate, capacity=384)
    assert int(c["dropped"]) == 128 and int(c_full["dropped"]) == 0
    with pytest.raises(ValueError, match="capacity"):
        moe_ffn_share(y, router, experts, gate, capacity=100)


def test_the_kernels_arithmetic_on_hand_counted_cases():
    from benchmarks.kernels import flash, flash_window, moe_grouped_train

    # 8 queries under a window of 3: 1 + 2 + 3 * 6 visible keys
    assert flash_window.band_pairs(8, 3) == 21
    assert flash_window.band_pairs(8, 8) == flash_window.band_pairs(8, 99) == 36
    assert flash_window.band_pairs(8192, 2048) / 8192 == 1792.125
    ops, nbytes = flash_window.fwd(1, 8, 2, 1, 4, 3)
    assert ops == 2 * 2 * 4 * 21 * 2
    assert nbytes == flash.fwd(1, 8, 2, 1, 4)[1]
    ops_b, _ = flash_window.bwd(1, 8, 2, 1, 4, 3)
    assert ops_b == 5 * 2 * 4 * 21 * 2
    # a window that reaches every key is the full kernel's count
    assert flash_window.fwd(2, 16, 4, 2, 8, 16) == flash.fwd(2, 16, 4, 2, 8)
    ev = '%flash_window_bwd_dq.3 = bf16[8,64,32]{2,1,0} custom-call(...), ' \
         'custom_call_target="tpu_custom_call"'
    assert flash_window.classify(ev) == "bwd" and flash.classify(ev) is None
    assert flash_window.classify(ev.replace("_window", "")) is None
    # 10 pairs to 2 experts of 4 x 3: nine products a pair
    ops, nbytes = moe_grouped_train.step_calls(10, 2, 4, 3)
    assert ops == 9 * 2 * 4 * 3 * 10
    assert nbytes == 2 * (9 * 4 * 3 * 2 + 10 * 9 * (4 + 3))
    dw = ev.replace("flash_window_bwd_dq", "grouped_matmul_dw")
    assert moe_grouped_train.classify(dw) == "dw"
    assert moe_grouped_train.classify(
        dw.replace("grouped_matmul_dw", "grouped_matmul")) == "gmm"


def test_train_flops_per_token_counts_this_share():
    cfg, bench_dir = _config(os.path.join(mf.ROOT, "BENCHMARK.json"),
                             "trinity-mini-train-c1")
    ref = mf.reference_of(cfg, bench_dir)
    a = ref.Arch.from_model(cfg)
    assert ref.keys_per_query(8192, 2048) == 1792.125
    assert ref.keys_per_query(8192) == 4096.5
    attn = 2048 * 128 * (3 * 32 + 2 * 4)                    # 27.3 M
    ffn = 3 * 2048 * 6144 + 4 * (2 * 3 * 2048 * 1024 + 2048 * 128)
    pairs = 4 * 1792.125 + 4096.5
    want = 3 * (2 * (5 * attn + ffn + 2048 * 25024) + 4 * pairs * 128 * 32)
    assert ref.train_flops_per_token(a, 8192) == want
    assert 2.1e9 < want < 2.3e9


@pytest.mark.parametrize("metric", [
    "moe_train_ms", "moe_route_ms", "moe_grouped_train_roofline",
    "flash_window_roofline", "moe_pairs_per_token_layer"])
def test_new_readers_read_nothing_where_there_is_nothing(metric):
    """A CPU rehearsal, an untraced run, or a program that counted nothing
    (a parent commit): None, never an exception."""
    from deepspeed_tpu.observability.hub import reset_hub

    reset_hub()
    arch = types.SimpleNamespace(
        hidden_size=8, moe_intermediate_size=4, num_experts=4, expert_layers=4,
        num_attention_heads=2, num_key_value_heads=1, head_dim=4,
        sliding_window=2048)
    ctx = types.SimpleNamespace(device={"platform": "cpu", "kind": "cpu"},
                                trace_dir="/nonexistent", note=lambda o: None)
    result = {"trace": None, "facts": {"arch": arch, "traced_steps": 0,
                                       "micro_per_chip": 1, "seq": 8}}
    assert mf.load_module("layer_metrics", metric).read(ctx, result) is None


def test_the_counter_readers_take_the_run_s_own_steps():
    """The roofline's floor comes from the traced steps' routing alone, the
    traffic's description from every timed step; the steps of set-up (the
    check's and the second warm-up's) belong to neither."""
    from benchmarks.harness import train_step
    from deepspeed_tpu.observability import StepTrace
    from deepspeed_tpu.observability.hub import get_hub, reset_hub

    reset_hub()
    hub = get_hub()
    # 2 steps of set-up, 3 traced, 4 in the window; 8 tokens x 4 layers
    for step, pairs in enumerate([99, 99, 32, 32, 32, 16, 16, 16, 16], 1):
        hub.record_step(StepTrace(step=step, wall_ms=1.0, extras={
            "moe_local_pairs": pairs, "moe_token_layers": 32,
            "moe_experts_hit": 16 if pairs > 16 else 8,
            "moe_max_expert_rows": pairs // 4, "moe_dropped_pairs": 0}))
    arch = types.SimpleNamespace(num_experts=4, expert_layers=4)
    result = {"attempted": 7, "facts": {"arch": arch, "traced_steps": 3}}
    c, n = train_step.counted(result, "traced")
    assert n == 3 and c["moe_local_pairs"] == 32
    c, n = train_step.counted(result)
    assert n == 7 and c["moe_local_pairs"] == (3 * 32 + 4 * 16) / 7
    assert train_step.counted()[1] == 9
    assert train_step.counted({"attempted": 12, "facts": {}}) is None
    notes = {}
    ctx = types.SimpleNamespace(note=notes.update)
    reader = mf.load_module("layer_metrics", "moe_pairs_per_token_layer")
    assert reader.read(ctx, result) == pytest.approx(160 / 7 / 32)
    note = notes["moe_pairs_per_token_layer"]
    assert note["by_step"] == {"smallest": 0.5, "largest": 1.0, "first": 1.0,
                               "last": 0.5}
    assert note["experts_hit_share"]["smallest"] == 0.5
    assert note["fullest_over_mean"]["first"] == 1.0
    reset_hub()
