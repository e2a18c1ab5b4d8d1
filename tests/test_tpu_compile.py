"""The main path's Pallas kernels, compiled for a TPU that is described
and not attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
a block whose last two dims break the (8, 128) tiling rule, a slice that
is not aligned, more VMEM than a kernel may hold. The TPU compiler ships
in the installation and compiles for a ``v5e:2x2`` topology description
without a chip (guide ``on-chip-measurement`` §2.3), so these compile
each kernel at the real ``mistral-7b`` widths — about two seconds each,
no chip time — and assert the Mosaic custom call is in the program.
Nothing runs: a pass here is not a chip run.
"""

import math
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.pallas import (flash_attention, gated_delta,
                                      grouped_matmul, paged_attention,
                                      quantization)

# mistral-7b attention geometry (models/zoo.py)
HQ, HKV, D = 32, 8, 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, as a sharding for abstract arguments."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # a described-device executable can be written to the persistent
    # cache but not read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True, scope="module")
def _compiled_as_for_the_chip():
    """tests/conftest.py has the suite's CPU programs compiled with most
    optimization off; what is compiled here is read (custom calls, copies,
    bytes) as the compiler leaves it for a chip."""
    prev = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", prev)


@pytest.fixture(autouse=True)
def _device_kernels(monkeypatch):
    """``jax.default_backend()`` is still the CPU here, so each module's
    ``_interpret()`` would pick the interpreter: steer it in the test."""
    for mod in (flash_attention, paged_attention, grouped_matmul,
                quantization, gated_delta):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _flash_loss(q, k, v, seg=None):
    out = flash_attention.flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=512, block_k=512)
    return jnp.sum(out.astype(jnp.float32))


def test_flash_fwd_bwd(chip):
    B, S = 2, 2048
    _compile(chip, jax.grad(_flash_loss, argnums=(0, 1, 2)),
             ((B, S, HQ, D), BF16), ((B, S, HKV, D), BF16),
             ((B, S, HKV, D), BF16))


@pytest.mark.parametrize("batch", [2, 8])
def test_flash_segment_ids_fwd_bwd(chip, batch):
    """The packed-sequence path: its [B, S] segment ids used to be
    blocked as (1, block), which Mosaic rejects for every B."""
    S = 2048
    _compile(chip, jax.grad(_flash_loss, argnums=(0, 1, 2)),
             ((batch, S, HQ, D), BF16), ((batch, S, HKV, D), BF16),
             ((batch, S, HKV, D), BF16), ((batch, S), jnp.int32))


def test_flash_padded_noncausal(chip):
    """Non-causal with S off the block grid synthesises segment ids."""
    def f(q, k, v):
        return flash_attention.flash_attention(q, k, v, causal=False,
                                               block_q=512, block_k=512)

    _compile(chip, f, ((2, 1000, HQ, D), BF16), ((2, 1000, HKV, D), BF16),
             ((2, 1000, HKV, D), BF16))


def test_flash_on_a_four_chip_mesh(chip):
    """GSPMD cannot partition a Mosaic kernel: the dispatcher has to wrap
    the call in a shard_map, or ZeRO-3 over fsdp=4 does not compile."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops import attention as attn_ops
    from deepspeed_tpu.parallel import topology
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    mesh = build_mesh(TopologyConfig(dp=1, fsdp=2, tp=2), devices=devices)
    topology.set_global_mesh(mesh)
    sh = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))

    def loss(q, k, v):
        out = attn_ops.multi_head_attention(q, k, v, causal=True,
                                            impl="flash")
        return jnp.sum(out.astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((4, 2048, n, D), BF16, sharding=sh)
            for n in (HQ, HKV, HKV)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text


# paged KV pool of one layer: [num_blocks, block_size, 2, kv_heads, D]
_POOL = ((512, 16, 2, HKV, D), BF16)


# S, heads, KV heads, head size, layers of the pool, blocks in it
_DECODE_SHAPES = {
    "mistral-7b-serve-c1": (32, HQ, HKV, D, 16, 2080),
    "qwen3-next-serve-c1": (32, 16, 2, 256, 2, 2080),
    "multi-head-256KiB-pages": (32, 32, 32, D, 2, 512),
    "tp2-shard-of-mistral": (32, HQ // 2, HKV // 2, D, 16, 2080),
}


@pytest.mark.parametrize("pages", [0, 1, 4])
@pytest.mark.parametrize("shape", sorted(_DECODE_SHAPES))
def test_paged_decode(chip, shape, pages):
    """The decode kernel at the cells' shapes (32 sequences, 64-entry
    block tables, 16-token pages), at the multi-head preset's 256 KiB
    pages (where the VMEM bound sizes the block) and at what a tp=2
    shard sees, on the whole pool at a traced layer; with the kernel's
    own block (0) and with explicit folds."""
    seqs, nh, nkv, hd, layers, blocks = _DECODE_SHAPES[shape]

    def f(q, kv, bt, ctx, layer):
        return paged_attention.paged_decode_attention(
            q, kv, bt, ctx, pages_per_compute_block=pages, layer=layer)

    text = _compile(chip, f, ((seqs, nh, hd), BF16),
                    ((layers, blocks, 16, 2, nkv, hd), BF16),
                    ((seqs, 64), jnp.int32), ((seqs,), jnp.int32),
                    ((), jnp.int32))
    # the pool goes in whole: no layer of it is sliced out or laid out anew
    assert f"bf16[{blocks},16,2,{nkv},{hd}]" not in text


@pytest.mark.parametrize("nkv,hd", [(1, 64), (12, 64), (HKV, 64)])
def test_paged_decode_on_a_pool_no_dma_can_slice(chip, nkv, hd):
    """Multi-query with one bf16 KV head, twelve heads of 64: a page's
    last two dims do not fill whole tiles, and the kernel keeps the
    pipelined walk over the block table."""
    assert not paged_attention._pages_sliceable(nkv, hd, 2)
    nh = 71 if nkv == 1 else nkv
    _compile(chip, paged_attention.paged_decode_attention,
             ((16, nh, hd), BF16), ((512, 16, 2, nkv, hd), BF16),
             ((16, 64), jnp.int32), ((16,), jnp.int32))


def test_paged_decode_under_tp2_shard_map(chip):
    """``model_runner._paged_decode`` on a tp=2 mesh: each shard runs
    the kernel on its 4 KV heads of the whole pool."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.inference.model_runner import _paged_decode
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    mesh = build_mesh(TopologyConfig(dp=2, tp=2), devices=devices)

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    args = (arg((32, HQ, D), BF16, None, "tp", None),
            arg((2, 2080, 16, 2, HKV, D), BF16,
                None, None, None, None, "tp", None),
            arg((), jnp.int32), arg((32, 64), jnp.int32),
            arg((32,), jnp.int32))
    text = jax.jit(lambda q, kv, l, bt, ctx: _paged_decode(
        mesh, q, kv, l, bt, ctx)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_prefill_tq64(chip):
    segs, tq, max_pages = 4, 64, 64
    _compile(chip, paged_attention.paged_prefill_attention,
             ((segs, tq, HQ, D), BF16), _POOL,
             ((segs, max_pages), jnp.int32), ((segs,), jnp.int32),
             ((segs,), jnp.int32))


@pytest.mark.parametrize("nh,nkv,bs,pages,tq", [
    (64, 4, 128, 400, 2048), (64, 4, 128, 400, 128), (32, 2, 64, 512, 2048)],
    ids=["selector-chunk", "selector-one-tile", "first-rule"])
def test_paged_block_prefill(chip, nh, nkv, bs, pages, tq):
    """The block-masked chunk kernel at ``minimax-m3-serve-c1``'s shapes (a
    chunk of 2,048 and of 128 queries, 16 heads a KV group, a table of 400
    pages of 128 tokens) and at the first rule's (``minicpm-sala-serve-c1``:
    2 KV heads of 16 query heads, pages of 64): the pool passes as it lies (the view of a
    page as rows is a bitcast, no copy of a pool), the visit lists fit the
    scalar memory, the page's rows are cut by strided loads of words."""
    text = _compile(
        chip, lambda q, kv, bt, m, p0, ctx, l:
        paged_attention.block_prefill_attention(q, kv, bt, m, p0, ctx,
                                                layer=l),
        ((1, tq, nh, D), BF16), ((5, 1664, bs, 2, nkv, D), BF16),
        ((1, pages), jnp.int32), ((1, tq, nkv, pages), jnp.bool_),
        ((1,), jnp.int32), ((1,), jnp.int32), ((), jnp.int32))
    assert "paged_block_prefill" in text
    pool = f"bf16[5,1664,{bs},2,{nkv},{D}]"
    assert not [line for line in text.splitlines()
                if pool in line.split("=")[0] and " copy(" in line]


def _serve_c1_two_layers(chip):
    """``mistral-7b-serve-c1`` at 2 of its 16 layers, as abstract arguments
    on the described chip: 2080 blocks of 16 tokens, 32 sequences of 64
    pages, 256 tokens a step. Returns (step programs, params, kv, ids)."""
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.models.zoo import get_model

    layers, blocks, bs, pages = 2, 2080, 16, 64
    model = get_model("mistral-7b", num_layers=layers,
                      max_seq_len=pages * bs, param_dtype=BF16, remat=False)
    cfg = model.config

    def ids(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    kv = jax.ShapeDtypeStruct(
        (layers, blocks, bs, 2, cfg.kv_heads, cfg.head_dim), BF16,
        sharding=chip)
    return engine_v2._shared_step_fns(cfg, None), params, kv, ids


SEQS, PAGES, TOKENS = 32, 64, 256


@pytest.mark.parametrize("program", ["decode", "multi_decode"])
def test_decode_programs_keep_the_pool_in_place(chip, program):
    """``mistral-7b-serve-c1`` at 2 of its 16 layers (2080 blocks of 16
    tokens, 32 sequences, 64 pages each): the program hands the pool back
    in the buffer it came in, and its temporaries stay under one *layer's*
    slice of the pool, so nowhere does it hold a second copy of a layer,
    let alone of the pool. (Before the pool became the scan's carry:
    alias 0, temporaries 137 MB for ``decode`` and 664 MB, 2.4 pools,
    for ``multi_decode``; the pool is 273 MB here.)"""
    fns, params, kv, ids = _serve_c1_two_layers(chip)
    pool_bytes = 2 * kv.size
    steps = {"steps": 8} if program == "multi_decode" else {}
    compiled = fns[program].lower(
        params, {"kv": kv}, ids(SEQS), ids(SEQS), ids(SEQS, PAGES),
        ids(SEQS), **steps).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // kv.shape[0]
    # and since the decode kernel reads the pool from HBM by its own
    # fetches (PR 31) they are what they were: 0.55 MB and 0.81 MB here,
    # 0.52 and 0.78 with the pipelined pages before it
    assert mem.temp_size_in_bytes < 2**20


def test_gather_program_reads_each_kv_head_once(chip):
    """The gather program (``jit_dstpu_serve_gather``) at the same shapes,
    256 tokens a step: grouped-query attention is computed per KV head, so
    the pool is handed back in its buffer, no per-query-head copy of the
    tokens' contexts exists in the compiled program (``[256,1024,8,4,128]``
    or ``[256,1024,32,128]``, in either order of context and heads), and
    the temporaries stay under 1 GiB. (A scratch compile read 0.63 GiB for
    this form, 0.76 GiB for the same contraction on a context laid out
    ``[T, Lmax, kv, hd]``, which the compiler relayouts, and 2.57 GiB for
    ``jnp.repeat`` of K and V, whose text held those two shapes 2 and 8
    times.)"""
    fns, params, kv, ids = _serve_c1_two_layers(chip)
    compiled = fns["step"].lower(
        params, {"kv": kv}, ids(TOKENS), ids(TOKENS), ids(TOKENS),
        ids(SEQS, PAGES), ids()).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * kv.size
    assert mem.temp_size_in_bytes < 2**30
    text = compiled.as_text()
    ctx, (nkv, hd) = PAGES * kv.shape[2], kv.shape[-2:]
    rep = HQ // nkv
    for shape in ((TOKENS, ctx, nkv, rep, hd), (TOKENS, ctx, HQ, hd),
                  (TOKENS, nkv, rep, ctx, hd), (TOKENS, HQ, ctx, hd)):
        assert "[" + ",".join(map(str, shape)) + "]" not in text, shape


@pytest.mark.parametrize("segs,tq", [(1, TOKENS), (2, TOKENS),
                                     (8, TOKENS // 4), (32, TOKENS // 16)])
def test_chunk_program_reads_a_sequence_context_once(chip, segs, tq):
    """The prefill program (``jit_dstpu_serve_prefill``) at the same shapes,
    one sequence's chunk of 256 rows a call, and two, eight of 64 and 32 of
    16 (padded layouts a call may have: ``S x tq <= 2 x max_tokens``): the
    attention is the plain product over each sequence's own 64 pages, no
    kernel. The pool is handed back in its buffer; no context a *token*
    exists in the text (``[256,8,1024,128]``, with or without the segment
    axis, in either order of heads and context); and the temporaries stay
    under 256 MiB (a scratch compile of one chunk read 0.5 MiB beside the
    31 MiB of float32 logits that the program hands out)."""
    fns, params, kv, ids = _serve_c1_two_layers(chip)
    compiled = fns["prefill"].lower(
        params, {"kv": kv}, ids(segs, tq), ids(segs), ids(segs),
        ids(segs, PAGES)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * kv.size
    assert mem.temp_size_in_bytes < 2**28
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    ctx, (nkv, hd) = PAGES * kv.shape[2], kv.shape[-2:]
    for shape in ((nkv, ctx, hd), (ctx, nkv, hd)):
        for lead in ((segs * tq,), (segs, tq)):
            assert "[" + ",".join(map(str, lead + shape)) + "]" not in text


def test_warm_up_leaves_a_window_of_mixed_steps_nothing_to_compile(
        monkeypatch):
    """A rehearsal on the CPU, interpreter kernels, the tiny fixture
    configuration: after the serving runner's ``warm_up`` a window in which
    prompts of every chunk bucket arrive among decoding sequences, alone
    and several a step, compiles nothing (the benchmark's own listener
    counts), and those steps were split by program."""
    monkeypatch.undo()          # this one runs: not the chip's kernels
    import numpy as np

    from benchmarks.generators.requests import Request, Served
    from benchmarks.harness import compiles
    from benchmarks.harness import manifest as mf
    from benchmarks.runners import serve

    _, bench_dir, _, cfg, _ = mf.resolve(
        os.path.join(os.path.dirname(__file__), "benchmarks", "fixtures",
                     "BENCHMARK.tiny.json"), "tiny-gen")
    arch = mf.reference_of(cfg, bench_dir).Arch.from_model(cfg)
    engine, _ = serve.build_engine(cfg, arch, 2147480011)
    served = Served(engine)
    compiles.install()
    serve.warm_up(served, cfg, arch.vocab_size)
    before, stats0 = compiles.count(), dict(engine.stats)
    assert before > 0                       # the listener hears this process

    rng = np.random.default_rng(5)
    rid = iter(range(1, 1000))

    def put(n, max_new):
        served.put(Request(next(rid), rng.integers(0, arch.vocab_size, n)
                           .astype(np.int32), max_new))

    for _ in range(4):                      # decoding sequences, long answers
        put(5, 120)
    # (33, 9, 9 and 20, 3, 3, 3, 3 do not pad into one call: two each)
    for arrivals in ([3], [9], [17], [33], [60], [40, 9], [12, 12, 12, 12],
                     [64], [100], [1], [2, 30], [33, 9, 9], [20, 3, 3, 3, 3]):
        for n in arrivals:
            put(n, 3)
        for _ in range(3):
            served.step()
    while served.outstanding:
        served.step()
    seen = compiles.count() - before
    assert seen == 0, compiles.SEEN[-seen:]
    new = {k: engine.stats[k] - stats0[k] for k in (
        "steps_dispatched", "calls_decode", "calls_multi_decode",
        "calls_prefill", "prefill_chunk_calls", "tokens_gather",
        "tokens_multi_decode", "prefill_gather_fallbacks")}
    # steps split by program: more calls than steps
    assert (new["calls_decode"] + new["calls_multi_decode"]
            + new["calls_prefill"]) - new["steps_dispatched"] >= 8
    assert new["calls_prefill"] == new["prefill_chunk_calls"] >= 14
    assert new["tokens_gather"] == 0 == new["prefill_gather_fallbacks"]
    assert new["tokens_multi_decode"] > 0
    engine.close()


def test_grouped_matmul_fwd_bwd(chip):
    """Mixtral-width expert matmul: M=8192 rows over E=8 experts."""
    M, K, N, E = 8192, 4096, 14336, 8

    def loss(lhs, rhs, sizes):
        return jnp.sum(grouped_matmul.gmm(lhs, rhs, sizes)
                       .astype(jnp.float32))

    _compile(chip, jax.grad(loss, argnums=(0, 1)),
             ((M, K), BF16), ((E, K, N), BF16), ((E,), jnp.int32))


@pytest.mark.parametrize("block", [512, 1024])
def test_flash_under_a_window_fwd_bwd(chip, block):
    """The band grid at the expert-training cell's shapes: 2 x 8192, 32
    query heads over 4, a window of 2048; the three kernels under their own
    names."""
    def loss(q, k, v):
        return jnp.sum(flash_attention.flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            window=2048).astype(jnp.float32))

    text = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                    ((2, 8192, 32, D), BF16), ((2, 8192, 4, D), BF16),
                    ((2, 8192, 4, D), BF16))
    for name in ("flash_window_fwd", "flash_window_bwd_dkdv",
                 "flash_window_bwd_dq"):
        assert name in text
    assert "flash_bwd_dq" not in text and "flash_bwd_dkdv" not in text


def test_grouped_matmul_bwd_on_a_half_empty_row_buffer(chip):
    """The expert-training cell's products: 24,576 buffer rows over 16
    experts of 2048 x 1024, forward and both gradients."""
    for K, N in ((2048, 1024), (1024, 2048)):
        def loss(lhs, rhs, sizes):
            return jnp.sum(grouped_matmul.gmm(lhs, rhs, sizes)
                           .astype(jnp.float32))

        text = _compile(chip, jax.grad(loss, argnums=(0, 1)),
                        ((24576, K), BF16), ((16, K, N), BF16),
                        ((16,), jnp.int32))
        assert "grouped_matmul_dw" in text


def test_kv_quantize_int8(chip):
    """One step's new K/V rows, one fp32 scale per head vector."""
    def f(x):
        return quantization.kv_quantize(x, bits=8)

    _compile(chip, f, ((256, 2, HKV, D), BF16))


# the hybrid configuration's kernels at its published widths (Qwen3-Next:
# 32 state heads of 128 x 128; 128 held experts of 2048 x 512 read by layer
# from the stack; attention heads of 256 in 2 KV groups of 8)


def test_gdn_decode_in_place_on_the_state_pool(chip):
    B, n, d = 32, 32, 128
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in (
        ((6, 49, n, d, d), f32), ((), jnp.int32), ((B,), jnp.int32),
        ((B, n, d), f32), ((B, n, d), f32), ((B, n, d), f32), ((B, n), f32),
        ((B, n), f32))]
    exe = jax.jit(gated_delta.gdn_decode, donate_argnums=(0,)).lower(
        *args).compile()
    assert "tpu_custom_call" in exe.as_text()
    mem = exe.memory_analysis()
    assert mem.temp_size_in_bytes < 2**20           # no copy of the pool
    assert mem.alias_size_in_bytes >= 6 * 49 * n * d * d * 4


def test_grouped_matmul_reads_a_layer_of_the_expert_stack(chip):
    def f(lhs, rhs, sizes, layer):
        return grouped_matmul.gmm_layer(lhs, rhs, sizes, layer)

    text = _compile(chip, f, ((384, 2048), BF16), ((8, 128, 2048, 512), BF16),
                    ((128,), jnp.int32), ((), jnp.int32))
    # the stack goes into the kernel whole: no slice of it is made
    assert "bf16[128,2048,512]" not in text.replace("bf16[8,128,2048,512]", "")


def test_paged_decode_at_head_size_256_group_8(chip):
    def f(q, kv, bt, ctx):
        return paged_attention.paged_decode_attention(q, kv, bt, ctx, layer=1)

    _compile(chip, f, ((32, 16, 256), BF16), ((2, 2080, 16, 2, 2, 256), BF16),
             ((32, 64), jnp.int32), ((32,), jnp.int32))


@pytest.mark.parametrize("segs,tq", [(1, 256), (8, 64)])
def test_qnext_chunk_program_keeps_both_pools_in_place(chip, segs, tq):
    """``qwen3-next-80b-a3b-serve-c1``'s prefill program at the cell's
    shapes (8 layers, 128 held experts, 2,080 pages of 16 tokens for the 2
    full layers, 48 state slots and the scratch slot for the 6 recurrent
    ones), a lone chunk of 256 rows and eight of 64, the two ends of what a
    split step hands it (``S x tq <= 2 x 256``): the paged pool, the state
    pool and the convolution tails come back in their buffers, the chunk
    attention is plain products (no Mosaic call named ``paged_prefill``,
    only the grouped products), and the temporaries stay under half a GiB
    beside 7.5 GiB of arguments (a scratch compile read 0.11 and 0.26 GiB;
    the gather program this replaces on the kernel path held 1.75)."""
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.zoo import get_model

    model = get_model("qwen3-next-80b-a3b", num_layers=8, max_seq_len=1024,
                      param_dtype=BF16, remat=False, vocab_size=37984,
                      experts_held=128, expert_offset=0)
    cfg = model.config

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                       jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    pools = {"kv": sds((2, 2080, 16, 2, cfg.kv_heads, cfg.head_dim), BF16),
             "state": sds((6, 49, 32, 128, 128), jnp.float32),
             "conv": sds((6, 49, 3, cfg.conv_channels), BF16)}
    held = sum(x.size * x.dtype.itemsize for x in pools.values())
    ids = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    compiled = engine_v2._shared_step_fns(cfg, None)["prefill"].lower(
        params, pools, ids(segs, tq), ids(segs), ids(segs), ids(segs, 64),
        ids(32)).compile()
    text = compiled.as_text()
    assert "grouped_matmul" in text and "paged_prefill" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**29
    assert 7.4 < mem.argument_size_in_bytes / 2**30 < 7.7


# the third architecture at its published widths and the cell's shapes
# (MiniCPM-SALA, layers 9-16: 32 lightning heads of 128 x 128; 2 KV heads of
# 128 in 64-token pages; 16,384 pages, 512 a sequence, 32 sequences)


def test_lightning_decode_in_place_on_the_state_pool(chip):
    B, n, d = 32, 32, 128
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in (
        ((6, 41, n, d, d), f32), ((), jnp.int32), ((B,), jnp.int32),
        ((B, n, d), f32), ((B, n, d), f32), ((B, n, d), f32), ((B, n), f32))]
    exe = jax.jit(gated_delta.lightning_decode, donate_argnums=(0,)).lower(
        *args).compile()
    assert "tpu_custom_call" in exe.as_text()
    mem = exe.memory_analysis()
    assert mem.temp_size_in_bytes < 2**20           # no copy of the pool
    assert mem.alias_size_in_bytes >= 6 * 41 * n * d * d * 4


def _sala_c1(chip):
    """``minicpm-sala-serve-c1`` as abstract arguments on the described chip.
    Returns (step programs, serving params, pools, ids, bytes of the pools)."""
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.inference.hybrid_runner import COUNTERS
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.zoo import get_model

    blocks, bs, pages, slots = 16384, 64, 512, 41
    model = get_model("minicpm-sala", num_layers=8, first_layer=9,
                      max_seq_len=pages * bs, param_dtype=BF16, remat=False)
    cfg = model.config

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                       jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    pools = {"kv": sds((2, blocks, bs, 2, 2, 128), BF16),
             "ck": sds((2, blocks, 4, 2, 128), BF16),
             "state": sds((6, slots, 32, 128, 128), jnp.float32),
             "conv": sds((6, slots, 0, 3 * 32 * 128), BF16),
             "counters": sds((len(COUNTERS),), jnp.int32)}
    held = sum(2 * x.size if x.dtype == BF16 else 4 * x.size
               for x in pools.values())
    return (engine_v2._shared_step_fns(cfg, None), params, pools,
            lambda *shape: sds(shape, jnp.int32), held)


@pytest.mark.parametrize("program", ["decode", "multi_decode", "prefill"])
def test_sala_programs_keep_the_three_pools_in_place(chip, program):
    """The paged pool (2 GiB, its pages ``[64, 2, 2, 128]`` bf16 tiled
    without padding), the compressed keys and the state pool come back in
    the buffers they came in, and no program holds a temporary near 2 GiB:
    the token step 0.07 GiB, the 8-step burst 0.9 (it lays the mixers'
    projections out anew once a burst), a 2,048-token chunk of one sequence
    0.7 (scores of 1,024 keys at a time; the sequence's own 32 MiB of pages
    a layer, held to the pool's layout so that the pool is not laid out anew
    for the gather)."""
    fns, params, pools, ids, held = _sala_c1(chip)
    S, pages = 32, 512
    if program == "prefill":
        lowered = fns["prefill"].lower(params, pools, ids(1, 2048), ids(1),
                                       ids(1), ids(1, pages), ids(S))
    else:
        steps = {"steps": 8} if program == "multi_decode" else {}
        lowered = fns[program].lower(params, pools, ids(S), ids(S),
                                     ids(S, pages), ids(S), ids(S), **steps)
    compiled = lowered.compile()
    text = compiled.as_text()
    if program != "prefill":
        assert "lightning_decode" in text and "paged_decode" in text
    mem = compiled.memory_analysis()
    # (all but the counters' vector, which a program hands out anew)
    assert mem.alias_size_in_bytes >= held - pools["counters"].size * 4
    assert mem.temp_size_in_bytes < 2**30
    # 5.25 GiB of weights + 2.54 GiB of pools
    assert 7.7 < mem.argument_size_in_bytes / 2**30 < 7.9


def _kimi_c1(chip):
    """``kimi-k2.7-code-serve-c1`` as abstract arguments on the described
    chip. Returns (step programs, serving params, pools, ids, the pool's
    bytes)."""
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.inference.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.inference.hybrid_runner import COUNTERS
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.zoo import get_model

    blocks, bs, pages = 14336, 64, 512
    model = get_model("kimi-k2", num_layers=5, experts_held=12,
                      vocab_size=20480, max_seq_len=pages * bs,
                      param_dtype=BF16, remat=False)
    cfg = model.config

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                       jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    kv = KVCacheConfig(num_layers=5, kv_heads=64, head_dim=192,
                       block_size=bs, num_blocks=blocks, dtype=BF16,
                       kind="latent", latent_dim=cfg.latent_dim)
    pools = {"kv": sds(kv.pool_shape, BF16),
             "counters": sds((len(COUNTERS),), jnp.int32)}
    return (engine_v2._shared_step_fns(cfg, None), params, pools,
            lambda *shape: sds(shape, jnp.int32),
            kv.num_blocks * kv.bytes_per_block)


@pytest.mark.parametrize("program", ["decode", "multi_decode", "prefill"])
def test_kimi_programs_keep_the_latent_pool_in_place(chip, program):
    """The latent pool (14,336 pages of 64 tokens of 640 bf16 lanes, five
    layers: 5.47 GiB, the 576 values a token padded to whole lane tiles as
    the device's layout pads them anyway) comes back in the buffer it came
    in; the token step holds the ``mla_decode`` kernel and the grouped
    product; a 2,048-token chunk of one sequence keeps its temporaries under
    1 GiB (scores of 512 context tokens at a time, their keys and values
    expanded for those 512 alone)."""
    fns, params, pools, ids, held = _kimi_c1(chip)
    S, pages = 48, 512
    if program == "prefill":
        lowered = fns["prefill"].lower(params, pools, ids(1, 2048), ids(1),
                                       ids(1), ids(1, pages))
    else:
        steps = {"steps": 8} if program == "multi_decode" else {}
        lowered = fns[program].lower(params, pools, ids(S), ids(S),
                                     ids(S, pages), ids(S), **steps)
    compiled = lowered.compile()
    text = compiled.as_text()
    if program != "prefill":
        assert "mla_decode" in text and "gmm" in text
    mem = compiled.memory_analysis()
    assert held == 5 * 14336 * 64 * 640 * 2
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**30
    # 6.51 GiB of weights + 5.47 GiB of pool
    assert 11.9 < mem.argument_size_in_bytes / 2**30 < 12.1
    print(program, mem.argument_size_in_bytes / 2**30,
          mem.temp_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30)


def _dots3_c1(chip):
    """``dots3-note-prev-serve-c1`` as abstract arguments on the described
    chip. Returns (step programs, serving params, pools, ids, the bytes of
    the three pools)."""
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.inference.ragged.kv_cache import (KVCacheConfig,
                                                         WindowPoolConfig)
    from deepspeed_tpu.inference.hybrid_runner import COUNTERS
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.zoo import get_model

    blocks, bs, pages, seqs = 16384, 64, 512, 48
    model = get_model("dots3-note", num_layers=9, experts_held=8,
                      vocab_size=19072, max_seq_len=pages * bs,
                      param_dtype=BF16, remat=False)
    cfg = model.config

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                       jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    kv = KVCacheConfig(num_layers=cfg.kv_layers, kv_heads=128, head_dim=192,
                       block_size=bs, num_blocks=blocks, dtype=BF16,
                       kind="latent", latent_dim=cfg.latent_dim,
                       index_key_dim=cfg.index_key_dim)
    win = WindowPoolConfig.for_sequences(
        seqs, layers=cfg.window_layers, window=513,
        row_dim=cfg.window_latent_dim, block_size=bs)
    ring = win.ring_pages
    pools = {"kv": sds(kv.pool_shape, BF16),
             "ik": sds(kv.pool_shape[:3] + (cfg.index_key_dim,), BF16),
             "wkv": sds(win.pool_shape, BF16),
             "counters": sds((len(COUNTERS),), jnp.int32)}
    held = kv.num_blocks * kv.bytes_per_block + 2 * math.prod(win.pool_shape)
    return (engine_v2._shared_step_fns(cfg, None), params, pools,
            lambda *shape: sds(shape, jnp.int32), held, ring)


@pytest.mark.parametrize("program", ["decode", "multi_decode", "prefill"])
def test_dots3_programs_keep_the_three_pools_in_place(chip, program):
    """The latent pool with the selector's keys beside it (16,384 pages of
    64 tokens, 640 + 128 bf16 lanes, three full layers: 4.5 GiB) and the
    windowed pool (48 rings of 10 pages and a scratch page, 1,152 lanes, six
    sliding layers: 0.4 GiB) come back in the buffers they came in; the
    token step holds the ``dsa_index`` and ``mla_decode`` kernels (the
    second over the chosen tokens and over the rings) and the grouped
    product; arguments are two thirds of the chip; temporaries: a token step
    0.26 GiB, an 8-step burst 0.57, a 2,048-token chunk of one sequence 1.40
    (the selector's scores and their bit keys ``[2048, 32768]`` twice 0.25,
    the expanded form's scores as Kimi's)."""
    fns, params, pools, ids, held, ring = _dots3_c1(chip)
    S, pages = 48, 512
    if program == "prefill":
        lowered = fns["prefill"].lower(params, pools, ids(1, 2048), ids(1),
                                       ids(1), ids(1, pages),
                                       window_table=ids(S, ring))
    else:
        steps = {"steps": 8} if program == "multi_decode" else {}
        lowered = fns[program].lower(params, pools, ids(S), ids(S),
                                     ids(S, pages), ids(S),
                                     window_table=ids(S, ring), **steps)
    compiled = lowered.compile()
    text = compiled.as_text()
    if program != "prefill":
        assert "mla_decode" in text and "gmm" in text and "dsa_index" in text
    mem = compiled.memory_analysis()
    assert ring == 10
    assert held == 3 * 16384 * 64 * 768 * 2 + 6 * 481 * 64 * 1152 * 2
    assert mem.alias_size_in_bytes >= held
    print(program, mem.argument_size_in_bytes / 2**30,
          mem.temp_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30)
    assert mem.temp_size_in_bytes < 1.5 * 2**30
    # 5.77 GiB of weights + 4.9 GiB of pools: 67% of the chip's 16
    assert 10.5 < mem.argument_size_in_bytes / 2**30 < 10.9


def _m3_c1(chip):
    """``minimax-m3-serve-c1`` as abstract arguments on the described chip:
    the step programs, their parameters (published layers 2-6, 8 of 128
    experts, an eighth of the vocabulary), the K/V pool with the pooled keys
    beside it, and the pools' bytes."""
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.inference.hybrid_runner import COUNTERS
    from deepspeed_tpu.inference.ragged import KVCacheConfig
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.zoo import get_model

    bs, blocks, pages = 128, 1664, 400
    model = get_model("minimax-m3", num_layers=5, first_layer=2,
                      experts_held=8, vocab_size=25088, max_seq_len=pages * bs,
                      param_dtype=BF16, remat=False)
    cfg = model.config

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda p: hybrid.serving_params(cfg, p),
                       jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    kv = KVCacheConfig(num_layers=cfg.kv_layers, kv_heads=4, head_dim=128,
                       block_size=bs, num_blocks=blocks, dtype=BF16,
                       pooled_key_dim=cfg.msa.index_dim)
    pools = {"kv": sds(kv.pool_shape, BF16),
             "pk": sds((5, blocks, 4, 128), BF16),
             "counters": sds((len(COUNTERS),), jnp.int32)}
    return (engine_v2._shared_step_fns(cfg, None), params, pools,
            lambda *shape: sds(shape, jnp.int32),
            kv.num_blocks * kv.bytes_per_block)


@pytest.mark.parametrize("program", ["decode", "multi_decode", "prefill"])
def test_m3_programs_keep_the_pool_and_the_pooled_keys_in_place(chip, program):
    """The K/V pool of 128-token pages (1,664 pages, five layers: 2.03 GiB)
    and the pooled keys beside it (8.5 MB) come back in the buffers they came
    in; the token step holds the paged decode kernel (over the pages each
    (sequence, KV head) chose) and the grouped product; arguments are half of
    the chip (5.94 GiB of weights at 8 held experts); a 2,048-token chunk of
    one sequence against a table of 400 pages holds the block-masked chunk
    kernel over the pool's own pages (no gathered keys, no score tensor
    outside the kernel) and its temporaries."""
    fns, params, pools, ids, held = _m3_c1(chip)
    S, pages = 8, 400
    if program == "prefill":
        lowered = fns["prefill"].lower(params, pools, ids(1, 2048), ids(1),
                                       ids(1), ids(1, pages))
    else:
        steps = {"steps": 8} if program == "multi_decode" else {}
        lowered = fns[program].lower(params, pools, ids(S), ids(S),
                                     ids(S, pages), ids(S), **steps)
    compiled = lowered.compile()
    text = compiled.as_text()
    if program != "prefill":
        assert "paged_decode" in text and "gmm" in text
    else:
        assert "paged_block_prefill" in text and "gmm" in text
    mem = compiled.memory_analysis()
    assert held == 5 * 1664 * (128 * 2 * 4 * 128 + 4 * 128) * 2
    assert mem.alias_size_in_bytes >= held
    print(program, mem.argument_size_in_bytes / 2**30,
          mem.temp_size_in_bytes / 2**30, mem.alias_size_in_bytes / 2**30)
    assert mem.temp_size_in_bytes < 3.0 * 2**30
    assert 7.8 < mem.argument_size_in_bytes / 2**30 < 8.2


@pytest.mark.parametrize("program", ["decode", "multi_decode", "prefill"])
def test_ouro_programs_hold_one_pool_of_192_slots(chip, program):
    """``ouro-2.6b-serve-c1`` whole, as its configuration file sizes it (48
    layers at the published widths, 337 blocks of 16 tokens, 16 sequences of
    28 pages): the pool has 4 x 48 = 192 layer slots (7.90 GiB) and comes
    back in the buffer it came in; arguments are the weights (4.97 GiB) and
    the pool and nothing of size beside; no instruction puts out a second
    pool. One loop over the slots keeps the token step's and the chunk
    call's temporaries under 2 MB; the 8-step burst holds a relayout of the
    ``wq``/``wk``/``wv`` stacks that XLA hoists out of its loop over the
    steps, 1.13 GiB (``deepspeed_tpu/inference/model_runner.py::
    _scan_layers``). The fullest program fits 15.75 GiB with 1.7 to
    spare."""
    import json

    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.models.zoo import get_model

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "ouro-2.6b-serve-c1.json")) as f:
        cell = json.load(f)
    e = cell["engine"]
    blocks, bs, pages, seqs = (e["kv_blocks"], e["kv_block_size"],
                               e["max_blocks_per_seq"], e["max_seqs_per_step"])
    model = get_model(cell["preset"], num_layers=cell["num_hidden_layers"],
                      max_seq_len=pages * bs, param_dtype=BF16, remat=False)
    cfg = model.config

    def ids(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    slots = cell["total_ut_steps"] * cell["num_hidden_layers"]
    kv = jax.ShapeDtypeStruct(
        (slots, blocks, bs, 2, cfg.kv_heads, cfg.head_dim), BF16,
        sharding=chip)
    fns = engine_v2._shared_step_fns(cfg, None)
    if program == "prefill":
        lowered = fns["prefill"].lower(params, {"kv": kv}, ids(1, 256),
                                       ids(1), ids(1), ids(1, pages))
    else:
        steps = {"steps": 8} if program == "multi_decode" else {}
        lowered = fns[program].lower(params, {"kv": kv}, ids(seqs), ids(seqs),
                                     ids(seqs, pages), ids(seqs), **steps)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert ("paged_decode" in text) == (program != "prefill")
    mem = compiled.memory_analysis()
    pool_bytes, gib = 2 * kv.size, 2**30
    assert slots == 192 and round(pool_bytes / gib, 2) == 7.9
    assert mem.alias_size_in_bytes >= pool_bytes
    assert 12.86 < mem.argument_size_in_bytes / gib < 12.88
    shape = f"bf16[{slots},{blocks},{bs},2,{cfg.kv_heads},{cfg.head_dim}]"
    assert not [line for line in text.splitlines()
                if shape in line.split("=")[0] and " copy(" in line]
    temp = mem.temp_size_in_bytes
    print(program, mem.argument_size_in_bytes / gib, temp / gib)
    if program == "multi_decode":
        assert 1.0 * gib < temp < 1.2 * gib
    else:
        assert temp < 2 * 2**20
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + temp)
    assert total < 14.1 * gib


def _zero3_fsdp4_step(monkeypatch, layers, job_extra=None):
    """The train step of ``mistral-7b-train-c4``'s job at ``layers`` of
    its 12, lowered on abstract state for the described 2x2: the engine
    as a job builds it, but for its state, which a described device
    cannot hold. Returns (engine, lowered step)."""
    import json

    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.config.config import load_config
    from deepspeed_tpu.models.zoo import get_model
    from deepspeed_tpu.ops import attention as attn_ops
    from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh
    from deepspeed_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(attn_ops, "_flash_available", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "mistral-7b-train-c4.json")) as f:
        cell = json.load(f)
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    mesh = build_mesh(TopologyConfig(**cell["mesh"]), devices=devices)
    whole = NamedSharding(mesh, P())

    def on(shapes, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            shapes, shardings)

    class AbstractState(engine_mod.Engine):
        def _build_state(self):
            param_sh = self.plan.param_shardings(self._axes)
            opt_sh = self.plan.opt_shardings(self._axes)

            def init_fn(rng):
                p32 = engine_mod._constrain_tree(self.model.init(rng), opt_sh)
                mp = engine_mod.init_mixed_precision(p32, self.tx,
                                                     shardings=opt_sh)
                params = jax.tree.map(
                    lambda m: m.astype(self.compute_dtype), mp.master)
                return engine_mod._constrain_tree(params, param_sh), mp

            rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole)
            with jax.set_mesh(mesh):
                placed = jax.jit(init_fn).lower(rng).compile().output_shardings
            self.params, self.opt_state = on(jax.eval_shape(init_fn, rng),
                                             placed)
            self._param_shardings, self._opt_shardings = param_sh, opt_sh
            self._setup_param_host_offload()
            scale = jax.eval_shape(
                lambda: engine_mod.init_loss_scale(self.config.fp16))
            self.loss_scale_state = on(scale, jax.tree.map(lambda _: whole,
                                                           scale))
            self.step_count = jax.ShapeDtypeStruct((), jnp.int32,
                                                   sharding=whole)

    model = get_model(cell["preset"], num_layers=layers,
                      max_seq_len=cell["seq_len"])
    engine = AbstractState(
        model, load_config(dict(cell["job"], seed=1, **(job_extra or {}))),
        mesh=mesh)
    batches = {"input_ids": jax.ShapeDtypeStruct(
        (1, engine.train_batch_size, cell["seq_len"]), jnp.int32,
        sharding=engine._batch_sharding(2))}
    with jax.set_mesh(mesh):
        lowered = engine._jit_train_step.lower(
            engine.params, engine.opt_state, engine.loss_scale_state,
            engine.step_count, batches)
    return engine, lowered


def test_zero3_fsdp4_step_reduces_gradients_outside_the_rings(
        chip, monkeypatch):
    """The four-chip cell's job, two layers of it: the engine hands the
    TPU's compiler the option its job calls for, the compiler knows it,
    and the backward scan of the compiled step holds the layer's weight
    gradients' reductions as products that reduce-scatter as they run,
    not as rings of collective-permutes: what is left of those is the
    windowed all-gather of a weight the forward scan reads once."""
    import re

    engine, lowered = _zero3_fsdp4_step(monkeypatch, layers=2)
    assert engine.layer_gather_ahead[0] == 0
    options = engine._train_step_compiler_options()
    assert options == {
        "xla_tpu_enable_windowed_einsum_for_reduce_scatter": False}
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce-scatter" in text
    # a ring hop of a gradient carries a quarter of a leaf in its own
    # layout: [1024, ...] of [4096, ...], or [..., 1024] of [..., 4096]
    hops = re.findall(
        r"= \(?(bf16\[[\d,]+\])[^=\n]* collective-permute-start\(", text)
    assert hops and not [h for h in hops if h in (
        "bf16[1024,14336]", "bf16[14336,1024]", "bf16[1024,32,128]",
        "bf16[32,128,1024]", "bf16[1024,8,128]")], hops


# the ninth architecture at its published widths and the cell's shapes
# (Nemotron 3 Nano, blocks 34-42: 8 held experts of 2688 x 1856 over a row
# buffer of 9,216; Mamba-2 of 64 heads of 64 in 8 groups, state 128, two
# sequences of 8,192 in chunks of 128)


def test_grouped_matmul_at_widths_that_are_no_power_of_two(chip):
    """``nemotron3-nano-train-c1``'s expert products, forward and backward:
    the expert width 1856 = 64 x 29 is no multiple of a lane tile and the
    hidden size 2688 = 128 x 21 halves down to one, so the tiles are the
    whole width and 384 (``_pick_lane_block``); Mosaic refuses 64-lane
    blocks, and 16 MiB of VMEM a block of 1856 x 896."""
    def f(x, wi, wo, sizes):
        def loss(x, wi, wo):
            hid = grouped_matmul.gmm(x, wi, sizes)
            out = grouped_matmul.gmm(jnp.square(jax.nn.relu(hid)), wo, sizes)
            return jnp.sum(out.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(x, wi, wo)

    text = _compile(chip, f, ((9216, 2688), BF16), ((8, 2688, 1856), BF16),
                    ((8, 1856, 2688), BF16), ((8,), jnp.int32))
    assert text.count("grouped_matmul_dw") >= 2


def test_mamba2_mixer_gradient_fits_beside_the_training_state(chip):
    """The Mamba-2 mixer of ``nemotron3-nano-train-c1`` (the chunked scan in
    plain ``jax.numpy``), value and gradient at two sequences of 8,192: XLA
    keeps no [chunk, chunk] decay matrix a head in float32 beside its masked
    product, and the whole backward's temporaries stay under 3.25 GiB (2.80
    here; the step program has some 5.6 beside 7.5 of state: AOT, PR 57)."""
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.zoo import get_model

    cfg = get_model("nemotron3-nano", num_layers=9, first_layer=34,
                    experts_held=8, vocab_size=16384).config
    shapes = hybrid._shapes(cfg)["mamba2"]
    mp = {k: jax.ShapeDtypeStruct(s[1:], BF16, sharding=chip)
          for k, s in shapes.items()}

    def f(mp, y):
        return jax.grad(lambda mp, y: jnp.sum(hybrid.mamba2_mixer(
            cfg, mp, y).astype(jnp.float32)), argnums=(0, 1))(mp, y)

    compiled = jax.jit(f).lower(mp, jax.ShapeDtypeStruct(
        (2, 8192, 2688), BF16, sharding=chip)).compile()
    mem = compiled.memory_analysis()
    print("mamba2 mixer grad: temp GiB", mem.temp_size_in_bytes / 2**30)
    assert mem.temp_size_in_bytes < 3.25 * 2**30
