"""The plain reference against ``models/transformer.py`` at a tiny size on
the CPU, the seed-made weights, and a lower-precision control that the
tolerance must fail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import compare, weights
from benchmarks.references import mistral

ARCH = mistral.Arch(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, intermediate_size=256, vocab_size=512,
                    num_hidden_layers=3, rope_theta=1e4, rms_norm_eps=1e-5)


def _model(dtype=jnp.float32):
    from deepspeed_tpu.models.zoo import get_model

    return get_model("mistral-7b", num_layers=3, max_seq_len=64, hidden_size=128,
                     num_heads=4, num_kv_heads=2, ffn_size=256, vocab_size=512,
                     dtype=dtype, param_dtype=jnp.float32, remat=False,
                     attn_impl="xla")


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 512, (2, 33)).astype(np.int32)


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 3])
def test_one_layer_alone_equals_its_slice_of_the_stacked_tree(seed):
    tree = weights.make_program_params(ARCH, seed, jnp.float32)
    layer_fn = weights.reference_layer_fn(ARCH, seed, jnp.float32)
    for l in range(3):
        w = layer_fn(l)
        assert np.array_equal(w["q_proj"], tree["layers"]["attn"]["wq"][l])
        assert np.array_equal(w["down_proj"], tree["layers"]["mlp"]["wo"][l])
        assert np.array_equal(w["input_layernorm"], tree["layers"]["ln1"]["scale"][l])
    top = weights.reference_top(ARCH, seed, jnp.float32)
    assert np.array_equal(top["lm_head"], tree["unembed"]["kernel"])
    assert np.array_equal(top["embed_tokens"], tree["embed"]["tokens"])
    # and the tree has the program's own layout
    want = jax.eval_shape(lambda: _model().init(jax.random.PRNGKey(0)))
    assert jax.tree.structure(want) == jax.tree.structure(tree)
    assert jax.tree.map(lambda a: a.shape, want) == jax.tree.map(lambda a: a.shape, tree)


# first three values and the last of six leaves, seed 5, at the committed
# configurations' widths, as ``harness/weights.py`` drew them before the
# table of leaves moved into the reference module (PR 27): the weights of
# every cell stay bit for bit what they were
PINNED = {
    ("attn.wq", 0): [0.005016559734940529, 0.02217281050980091,
                     0.009176946245133877, 0.009444541297852993],
    ("mlp.wo", 1): [0.007860644720494747, -0.014517283998429775,
                    -0.005434690974652767, -0.010122771374881268],
    ("ln2.scale", 15): [1.0174803733825684, 1.0183157920837402,
                        1.0977587699890137, 1.1563602685928345],
    ("embed.tokens", None): [-0.005918500944972038, -0.00982873048633337,
                             0.03801785781979561, 0.007151418831199408],
    ("unembed.kernel", None): [-0.009967577643692493, 0.012947824783623219,
                               0.022397883236408234, 0.009254544973373413],
    ("final_norm.scale", None): [0.854008138179779, 1.0801080465316772,
                                 1.020498514175415, 1.0363866090774536],
}


@pytest.mark.parametrize("path,layer", sorted(PINNED, key=str))
def test_weights_of_the_committed_configurations_are_what_they_were(path, layer):
    import json
    import os

    from benchmarks.harness import manifest as mf

    with open(os.path.join(mf.BENCH_DIR, "configs", "mistral-7b-serve-c1.json")) as f:
        cfg = json.load(f)
    arch = mf.reference_of(cfg).Arch.from_model(cfg)
    leaf = next(x for x in arch.leaf_table() if x.path == path)
    got = np.asarray(weights.draw_leaf(leaf, weights.base_key(5), layer,
                                       jnp.float32)).ravel()
    assert [float(v) for v in got[:3]] + [float(got[-1])] == PINNED[path, layer]
    if path == "attn.wq":        # and in the type they are served in
        low = np.asarray(weights.draw_leaf(leaf, weights.base_key(5), 0, jnp.bfloat16)
                         .astype(jnp.float32)).ravel()
        assert [float(v) for v in low[:3]] == [0.0050048828125, 0.022216796875,
                                               0.0091552734375]


def _mistral_configurations():
    """(name, configuration) of every committed configuration whose
    ``reference`` is ``mistral``: the manifest holds other architectures
    since PR 28, each with a table of leaves of its own."""
    import json
    import os

    from benchmarks.harness import manifest as mf

    out = []
    for c in mf.load_manifest()["configs"]:
        with open(os.path.join(mf.ROOT, c["file"])) as f:
            cfg = json.load(f)
        if cfg["reference"] == "mistral":
            out.append((c["name"], cfg))
    return out


MISTRAL_CONFIGURATIONS = _mistral_configurations()


@pytest.mark.parametrize("cfg", [c for _, c in MISTRAL_CONFIGURATIONS],
                         ids=[n for n, _ in MISTRAL_CONFIGURATIONS])
def test_the_three_committed_configurations_share_one_table_of_leaves(cfg):
    from benchmarks.harness import manifest as mf

    def table(c):
        return mf.reference_of(c).Arch.from_model(c).leaf_table()

    others = MISTRAL_CONFIGURATIONS
    assert len(others) == 3
    assert [x.path for x in table(cfg) if x.per_layer] == [
        "attn.wq", "attn.wk", "attn.wv", "attn.wo", "mlp.wg", "mlp.wi",
        "mlp.wo", "ln1.scale", "ln2.scale"]
    # depth is not in the table
    assert all(table(c) == table(cfg) for _, c in others)


def test_weights_differ_by_seed_and_serve_dtype_rounds_the_same_draws():
    a = weights.reference_layer_fn(ARCH, 1, jnp.float32)(0)["q_proj"]
    b = weights.reference_layer_fn(ARCH, 2, jnp.float32)(0)["q_proj"]
    assert not np.array_equal(a, b)
    low = weights.reference_layer_fn(ARCH, 1, jnp.bfloat16)(0)["q_proj"]
    assert np.array_equal(low, a.astype(jnp.bfloat16).astype(jnp.float32))


def test_logits_match_the_programs_forward_in_float32(tokens):
    model = _model()
    params = weights.make_program_params(ARCH, 3, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply(params, jnp.asarray(tokens[:, :-1])))
    got = mistral.forward_logits(
        ARCH, [tokens[0, :-1], tokens[1, :-1]], [range(32), range(32)],
        weights.reference_layer_fn(ARCH, 3, jnp.float32),
        weights.reference_top(ARCH, 3, jnp.float32))
    for b in range(2):
        assert compare.rel_l2(got[b], want[b]) < 1e-5


def _program_loss_and_grads(model, params, tokens):
    def loss(p):
        return model.loss(p, {"input_ids": jnp.asarray(tokens)})[0]
    return jax.value_and_grad(loss)(params)


def test_loss_and_sampled_gradients_match_the_program(tokens):
    model = _model()
    params = weights.make_program_params(ARCH, 4, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_loss, want = _program_loss_and_grads(model, params, tokens)
    kept = {}
    out = mistral.loss_and_grads(
        ARCH, tokens, weights.reference_layer_fn(ARCH, 4, jnp.float32),
        weights.reference_top(ARCH, 4, jnp.float32),
        lambda name, g: np.asarray(g))
    assert out["loss"] == pytest.approx(float(want_loss), rel=1e-5)
    gn = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(want))))
    assert out["grad_norm"] == pytest.approx(gn, rel=1e-4)
    for name in ("layers.1.q_proj", "layers.2.down_proj", "layers.0.input_layernorm",
                 "norm", "lm_head", "embed_tokens", "layers.1.k_proj",
                 "layers.0.gate_proj"):
        path = weights.program_leaf_name(ARCH, name).split(".")
        leaf = want
        for p in path:
            leaf = leaf[p]
        if name.startswith("layers."):
            leaf = leaf[int(name.split(".")[1])]
        assert compare.rel_l2(out["kept"][name], leaf) < 1e-4, name


@pytest.mark.parametrize("numerics,floor", [("bf16", 3e-3), ("fp8", 3e-2)])
def test_a_lower_precision_control_leaves_the_tolerance(tokens, numerics, floor):
    """The control is the reference computed one step down; the float32
    tolerance (1e-4 above) must refuse it by a wide margin."""
    args = (ARCH, [tokens[0, :-1]], [range(32)],
            weights.reference_layer_fn(ARCH, 3, jnp.float32),
            weights.reference_top(ARCH, 3, jnp.float32))
    want = mistral.forward_logits(*args)[0]
    got = mistral.forward_logits(*args, numerics=numerics)[0]
    assert compare.rel_l2(got, want) > floor
    keep = lambda name, g: np.asarray(g) if name == "layers.1.q_proj" else None
    ref = mistral.loss_and_grads(ARCH, tokens, args[3], args[4], keep)
    low = mistral.loss_and_grads(ARCH, tokens, args[3], args[4], keep, numerics)
    assert compare.rel_l2(low["kept"]["layers.1.q_proj"],
                          ref["kept"]["layers.1.q_proj"]) > floor


def test_verdict_needs_every_number_inside_its_limit():
    v = compare.Verdict()
    assert not v.correct                      # nothing compared: not correct
    v.hold("a", 0.01, 0.02)
    assert v.correct
    v.hold("b", float("nan"), 0.02)
    assert not v.correct
    v2 = compare.Verdict()
    v2.hold("a", 0.03, 0.02)
    assert not v2.correct
