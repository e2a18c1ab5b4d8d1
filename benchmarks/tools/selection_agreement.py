"""How often the program's block selection is the reference's, and what a
disagreement costs: the configuration's first ``minicpm4`` layer on a context
of the cell's length, its queries, keys and compressed keys computed once in
float32 (the reference's arithmetic) and once as the program computes them
(bfloat16 projections, compressed keys rounded to bfloat16, float32 scores),
each put through its own implementation of the rule.

    python3 benchmarks/tools/selection_agreement.py --workload \
        serve-sala-longctx-decode --seeds 1,2,3 [--context 24576]

One JSON line a seed: the share of (query, KV head) selections that are the
same set of blocks, the mean number of blocks that differ in one that is not,
and the relative L2 distance of the layer's attention output (before the
gate) under the program's choice from that under the reference's, over the
selections that differ and over all. Held to no limit: the check judges the
logits. Needs a chip at the real size (``--rehearse`` for a fixture on the
CPU).
"""

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import manifest as mf  # noqa: E402


def one_seed(cfg, ref, arch, seed: int, context: int, queries: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import weights
    from benchmarks.references.mistral import rms_norm
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.ops import block_sparse

    layer = next(l for l in range(arch.num_hidden_layers) if arch.is_sparse(l))
    if layer:
        raise SystemExit("the first layer held is not a minicpm4 layer: its "
                         "input would need the layers before it")
    model = get_model(cfg["preset"], num_layers=arch.num_hidden_layers,
                      max_seq_len=context, **cfg.get("preset_overrides", {}))
    c = model.config
    sz = c.sparse
    w = weights.reference_layer_fn(arch, seed, jnp.bfloat16)(layer)
    top = weights.reference_top(arch, seed, jnp.bfloat16)
    rng = np.random.default_rng([seed, 0x73656C])
    ids = rng.integers(0, arch.vocab_size, context)
    x = arch.scale_emb * top["embed_tokens"][jnp.asarray(ids)]
    t = jnp.asarray(np.sort(rng.choice(
        np.arange(sz.dense_len, context), queries, replace=False)), jnp.int32)
    nkv, d = arch.num_key_value_heads, arch.head_dim
    g = arch.num_attention_heads // nkv
    blocks = -(-context // sz.block)

    @jax.jit
    def both(x, w, t):
        with jax.default_matmul_precision("highest"):
            y = rms_norm(x, w["input_layernorm"], arch.rms_norm_eps)
            k = rms_norm(jnp.einsum("th,hnd->tnd", y, w["k_proj"]),
                         w["k_norm"], arch.rms_norm_eps)
            v = jnp.einsum("th,hnd->tnd", y, w["v_proj"])
            q = rms_norm(jnp.einsum("th,hnd->tnd", y[t],
                                    w["q_gate_proj"][..., :d]),
                         w["q_norm"], arch.rms_norm_eps).reshape(-1, nkv, g, d)
            W = (context - sz.kernel) // sz.stride + 1
            at = (sz.stride * jnp.arange(W))[:, None] + jnp.arange(sz.kernel)
            want = ref.chosen_blocks(arch, q, jnp.mean(k[at], axis=1), t,
                                     blocks)
        # the program's arithmetic on the same input
        ap = {"wq": w["q_gate_proj"], "wk": w["k_proj"], "wv": w["v_proj"],
              "q_norm": w["q_norm"], "k_norm": w["k_norm"]}
        ap = jax.tree.map(lambda a: a.astype(jnp.bfloat16), ap)
        yb = hybrid._rms(x.astype(jnp.bfloat16),
                         w["input_layernorm"].astype(jnp.bfloat16),
                         c.norm_eps)
        _, kb, _, _ = hybrid.attn_project(c, ap, yb, jnp.arange(context))
        qb, _, _, _ = hybrid.attn_project(c, ap, yb[t], t)
        pad = blocks * sz.per_block
        atp = jnp.minimum((sz.stride * jnp.arange(pad))[:, None]
                          + jnp.arange(sz.kernel), context - 1)
        ckb = block_sparse.compress_windows(kb[atp]).astype(jnp.bfloat16)
        idx, _, _ = block_sparse.select_blocks(
            sz, qb.reshape(-1, nkv, g, d), ckb, t, 1.0 / math.sqrt(d))
        got = block_sparse.block_mask(sz, idx, t, blocks)

        def attend(mask):           # float32 attention under a block mask
            with jax.default_matmul_precision("highest"):
                ok = (jnp.repeat(mask, sz.block, axis=-1)[..., :context]
                      & (jnp.arange(context)[None, :] <= t[:, None])[:, None])
                s = jnp.einsum("qkgd,tkd->qkgt", q, k) / math.sqrt(d)
                p = jax.nn.softmax(jnp.where(ok[:, :, None], s, -jnp.inf), -1)
                return jnp.einsum("qkgt,tkd->qkgd", p, v)

        return want, got, attend(want), attend(got)

    want, got, o_want, o_got = (np.asarray(a) for a in both(x, w, t))
    differ = np.sum(want != got, axis=-1)               # [Q, nkv]
    same = differ == 0

    def rel(a, b):
        return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))

    return {"seed": seed, "context": context, "selections": int(same.size),
            "agree_share": float(same.mean()),
            "blocks_differing_where_not": float(differ[~same].mean() / 2)
            if (~same).any() else 0.0,
            "attn_rel_l2_where_not": rel(o_got[~same], o_want[~same])
            if (~same).any() else 0.0,
            "attn_rel_l2_all": rel(o_got, o_want)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--context", type=int, default=None)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--manifest", default=os.path.join(mf.ROOT,
                                                       "BENCHMARK.json"))
    args = ap.parse_args()
    _, bench_dir, _, cfg, traffic = mf.resolve(args.manifest, args.workload)
    mf.program_logs_to_stderr()
    ref = mf.reference_of(cfg, bench_dir)
    arch = ref.Arch.from_model(cfg)
    context = args.context or traffic["prompt_tokens"]["hi"]
    for seed in (int(s) for s in args.seeds.split(",") if s):
        print(json.dumps(one_seed(cfg, ref, arch, seed, context,
                                  args.queries)), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
