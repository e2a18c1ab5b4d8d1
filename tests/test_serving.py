"""Serving-layer tests: admission queue + preempt-and-requeue, shared-
prefix KV cache, speculative decoding, scheduler fairness, the SLO
harness schema, and the serving config block.

The load-bearing guarantees (docs/serving.md):
- put() never drops or errors a request the pool could ever fit — full
  pools queue, exhaustion mid-decode preempts-and-requeues, and every
  request eventually completes with its full token budget;
- shared-prefix KV reuse and speculative greedy decoding are pure
  optimizations: token streams are bit-identical with them on or off.
"""

import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.ragged import (BlockedAllocator, BlockedKVCache,
                                            PrefixCache)
from deepspeed_tpu.inference.ragged.sequence import StateManager
from deepspeed_tpu.inference.scheduler import SplitFuseScheduler
from deepspeed_tpu.inference.spec_decode import Drafter, PromptLookupDrafter
from deepspeed_tpu.models.zoo import get_model


@pytest.fixture(scope="module")
def tiny():
    model = get_model("tiny", dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_engine(tiny, **kw):
    from deepspeed_tpu.inference import InferenceEngineV2

    model, params = tiny
    kw.setdefault("kv_blocks", 64)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("max_tokens_per_step", 32)
    kw.setdefault("max_seqs_per_step", 4)
    kw.setdefault("max_blocks_per_seq", 8)
    return InferenceEngineV2(model, params=params, dtype=jnp.float32, **kw)


# -- prefix cache (host bookkeeping only) --------------------------------


class TestPrefixCache:
    def test_chain_lookup_and_refcounts(self):
        c = PrefixCache(block_size=4)
        toks = np.arange(8, dtype=np.int32)
        k1 = c.chain_key(None, toks[:4])
        k2 = c.chain_key(k1, toks[4:8])
        assert c.register(k1, 10) and c.register(k2, 11)
        keys, blocks = c.lookup(np.concatenate([toks, [99]]))
        assert keys == [k1, k2] and blocks == [10, 11]
        # a divergent second block breaks the chain at block 1
        bad = toks.copy()
        bad[5] = 77
        keys, blocks = c.lookup(bad)
        assert keys == [k1] and blocks == [10]
        # register held one ref each; drop them -> idle/evictable
        c.unref([k1, k2])
        assert c.evictable_blocks == 2
        c.ref([k1])  # revive from idle
        assert c.evictable_blocks == 1
        with pytest.raises(KeyError):
            c.ref(["deadbeef"])
        with pytest.raises(ValueError):
            c.unref([k2])  # already idle

    def test_register_conflict_keeps_block_private(self):
        c = PrefixCache(block_size=4)
        key = c.chain_key(None, [1, 2, 3, 4])
        assert c.register(key, 5)
        assert not c.register(key, 6)  # same content, different block
        assert c.stats["conflicts"] == 1
        # re-register of the SAME block just takes another ref
        assert c.register(key, 5)
        c.unref([key])
        assert c.evictable_blocks == 0  # one ref still held

    def test_evict_only_idle_lru_order(self):
        c = PrefixCache(block_size=2)
        k1 = c.chain_key(None, [1, 1])
        k2 = c.chain_key(None, [2, 2])
        k3 = c.chain_key(None, [3, 3])
        for k, b in ((k1, 1), (k2, 2), (k3, 3)):
            c.register(k, b)
        c.unref([k2])
        c.unref([k1])
        # k3 still referenced: eviction may only return the idle two, in
        # least-recently-idle order (k2 idled first)
        assert c.evict(10) == [2, 1]
        assert c.cached_blocks == 1
        assert c.lookup([2, 2])[0] == []
        assert c.stats["evicted"] == 2


# -- scheduler fairness / starvation grid --------------------------------


class _FakeKV(BlockedKVCache):
    """StateManager's kv_cache surface without device memory: the pages'
    host side alone."""

    unsupported = frozenset()

    def __init__(self, blocks, block_size=8):
        self.allocator = BlockedAllocator(blocks)
        self.block_size = block_size
        self.prefix_cache = self.host_tier = None
        self.stores = [self]

    def blocks_needed(self, n):
        return -(-n // self.block_size)

    def reclaim(self, n):
        return 0


class TestSchedulerFairness:
    def _state(self, blocks=64, max_blocks_per_seq=8):
        return StateManager(_FakeKV(blocks),
                            max_blocks_per_seq=max_blocks_per_seq)

    def test_decode_scheduled_before_prefill(self):
        state = self._state()
        d = state.get_or_create(1, np.arange(4, dtype=np.int32))
        d.seen_tokens = 4  # in decode
        state.get_or_create(2, np.arange(10, dtype=np.int32))
        sched = SplitFuseScheduler(state, max_tokens_per_step=8,
                                   max_seqs_per_step=4).schedule()
        assert [s.uid for s, _, _ in sched] == [1, 2]
        assert len(sched[0][1]) == 1          # one decode token
        assert len(sched[1][1]) == 7          # prefill fills the rest

    def test_budget_exhaustion_counts_starvation(self):
        state = self._state()
        for uid in (1, 2, 3):
            state.get_or_create(uid, np.arange(10, dtype=np.int32))
        sched = SplitFuseScheduler(state, max_tokens_per_step=10,
                                   max_seqs_per_step=4)
        out = sched.schedule()
        assert len(out) == 1  # first chunk ate the whole budget
        assert sched.stats["prefill_starvation_steps"] == 1

    def test_slot_exhaustion_counts_starvation(self):
        state = self._state()
        for uid in (1, 2):
            state.get_or_create(uid, np.arange(4, dtype=np.int32))
        sched = SplitFuseScheduler(state, max_tokens_per_step=64,
                                   max_seqs_per_step=1)
        assert len(sched.schedule()) == 1
        assert sched.stats["prefill_starvation_steps"] == 1

    def test_kv_starved_seq_skipped_not_fatal(self):
        state = self._state(blocks=1)
        state.get_or_create(1, np.arange(30, dtype=np.int32))  # needs 4
        sched = SplitFuseScheduler(state, max_tokens_per_step=64,
                                   max_seqs_per_step=4)
        assert sched.schedule() == []
        assert sched.stats["kv_starved_skips"] == 1

    def test_prefill_scan_round_robins(self):
        """With budget for only one chunk per step, leftover budget must
        rotate over waiting prompts instead of re-feeding the oldest."""
        state = self._state()
        for uid in (1, 2, 3):
            state.get_or_create(uid, np.arange(100, dtype=np.int32),
                                max_new_tokens=1)
        sched = SplitFuseScheduler(state, max_tokens_per_step=8,
                                   max_seqs_per_step=4)
        first_uids = [sched.schedule()[0][0].uid for _ in range(3)]
        assert sorted(first_uids) == [1, 2, 3], first_uids


# -- speculative decoding ------------------------------------------------


class TestSpecDecode:
    def test_prompt_lookup_drafter(self):
        d = PromptLookupDrafter(max_ngram=3)
        # history ends [1,2,3]; same trigram occurred at pos 0 -> propose
        # what followed it
        assert d.propose([1, 2, 3, 4, 5, 1, 2, 3], k=2) == [4, 5]
        # most recent earlier match wins
        assert d.propose([7, 9, 7, 8, 7], k=1) == [8]
        assert d.propose([1, 2, 3, 4], k=4) == []  # no repeat
        assert d.propose([1], k=4) == []
        with pytest.raises(ValueError):
            PromptLookupDrafter(max_ngram=2, min_ngram=3)
        assert isinstance(d, Drafter)

    def test_spec_greedy_bit_identical(self, tiny):
        prompts = {1: [5, 6, 7, 5, 6, 7, 5, 6], 2: [1, 2, 1, 2, 1, 2, 1],
                   3: [9, 9, 9, 9, 9], 4: [3, 14, 15, 9, 2, 6]}
        runs = {}
        for spec in (False, True):
            eng = make_engine(tiny, spec_decode=spec, spec_k=4)
            eng.put(list(prompts), [np.asarray(p, np.int32)
                                    for p in prompts.values()],
                    max_new_tokens=12)
            runs[spec] = (eng.generate_all(), dict(eng.stats))
        out_base, _ = runs[False]
        out_spec, stats = runs[True]
        assert out_spec == out_base  # token-identical, per uid
        # the speculative path actually ran and proposed drafts
        assert stats["spec_steps"] > 0 and stats["spec_proposed"] > 0

    def test_transformer_drafter_greedy_bit_identical(self, tiny):
        """A real (tiny, from-scratch) draft model behind the Drafter
        protocol: proposals actually flow through the verify path and
        greedy output stays token-identical to the no-spec engine —
        acceptance gates correctness, the draft only buys throughput."""
        from deepspeed_tpu.inference.spec_decode import TransformerDrafter

        model, _ = tiny
        drafter = TransformerDrafter.small(model.config.vocab_size,
                                           window=16, seed=1)
        assert isinstance(drafter, Drafter)
        prompts = {1: [5, 6, 7, 5, 6, 7, 5, 6], 2: [1, 2, 1, 2, 1, 2, 1],
                   3: [3, 14, 15, 9, 2, 6]}
        base = make_engine(tiny)
        base.put(list(prompts), [np.asarray(p, np.int32)
                                 for p in prompts.values()],
                 max_new_tokens=10)
        ref = base.generate_all()
        eng = make_engine(tiny, drafter=drafter, spec_k=3)
        eng.put(list(prompts), [np.asarray(p, np.int32)
                                for p in prompts.values()],
                max_new_tokens=10)
        assert eng.generate_all() == ref  # token-identical, per uid
        assert drafter.stats["proposals"] > 0
        assert drafter.stats["proposed_tokens"] >= drafter.stats["proposals"]
        assert eng.stats["spec_proposed"] > 0
        # an untrained draft rarely matches the target's argmax chain:
        # acceptance may be low but never exceeds what was proposed
        assert eng.stats["spec_accepted"] <= eng.stats["spec_proposed"]

    def test_transformer_drafter_window_and_edge_cases(self):
        from deepspeed_tpu.inference.spec_decode import TransformerDrafter

        d = TransformerDrafter.small(64, window=8)
        out = d.propose(list(range(20)), k=3)  # history > window: trails
        assert len(out) == 3 and all(0 <= t < 64 for t in out)
        # deterministic: same history, same proposal
        assert d.propose(list(range(20)), k=3) == out
        assert d.propose([], k=3) == []
        assert d.propose([1, 2, 3], k=0) == []
        assert d.stats["empty"] == 2
        with pytest.raises(ValueError, match="window"):
            TransformerDrafter.small(64, window=1)

    def test_custom_drafter_hook_cannot_corrupt_output(self, tiny):
        class JunkDrafter:
            def propose(self, tokens, k):
                return [0] * k  # deliberately terrible drafts

        assert isinstance(JunkDrafter(), Drafter)
        prompts = [np.asarray([4, 8, 15, 16, 23, 42], np.int32)]
        ref_eng = make_engine(tiny)
        ref_eng.put([1], prompts, max_new_tokens=8)
        ref = ref_eng.generate_all()
        eng = make_engine(tiny, drafter=JunkDrafter(), spec_k=3)
        eng.put([1], prompts, max_new_tokens=8)
        assert eng.generate_all() == ref
        assert eng.stats["spec_proposed"] > 0
        # junk drafts mostly rejected: acceptance well under proposal
        assert eng.stats["spec_accepted"] <= eng.stats["spec_proposed"]


# -- shared-prefix reuse through the engine ------------------------------


class TestPrefixReuse:
    def test_second_request_skips_cached_prefill(self, tiny):
        eng = make_engine(tiny)
        prompt = np.arange(20, dtype=np.int32) % 100
        eng.put([1], [prompt], max_new_tokens=4)
        first = eng.generate_all()
        cold_prefill = eng.scheduler.stats["prefill_tokens"]
        assert cold_prefill == 20
        eng.put([2], [prompt], max_new_tokens=4)
        second = eng.generate_all()
        # two full 8-token blocks came from the cache; only the prompt
        # tail (and never the final token's logits) re-prefilled
        assert eng.stats["prefix_hit_tokens"] == 16
        assert eng.scheduler.stats["prefill_tokens"] - cold_prefill == 4
        assert second[2] == first[1]  # shared KV is bit-equivalent

    def test_divergent_tail_copy_on_write(self, tiny):
        base = np.arange(16, dtype=np.int32)
        a = np.concatenate([base, [50, 51, 52, 53]]).astype(np.int32)
        b = np.concatenate([base, [60, 61, 62, 63]]).astype(np.int32)
        ref_eng = make_engine(tiny, prefix_cache=False)
        ref_eng.put([1, 2], [a, b], max_new_tokens=6)
        ref = ref_eng.generate_all()
        eng = make_engine(tiny)
        eng.put([1], [a], max_new_tokens=6)
        out = eng.generate_all()
        eng.put([2], [b], max_new_tokens=6)
        out.update(eng.generate_all())
        # request 2 shares request 1's first two blocks but its divergent
        # tail stays private — outputs match the cache-off engine exactly
        assert eng.stats["prefix_hit_tokens"] == 16
        assert out == ref

    def test_idle_cached_blocks_evicted_under_pressure(self, tiny):
        eng = make_engine(tiny, kv_blocks=9, max_blocks_per_seq=8)
        eng.put([1], [np.arange(20, dtype=np.int32)], max_new_tokens=2)
        eng.generate_all()
        cache = eng.kv_cache.prefix_cache
        assert cache.evictable_blocks == 2  # released but still cached
        # a content-disjoint prompt needing more blocks than the free
        # list reclaims them
        eng.put([2], [(np.arange(52, dtype=np.int32) + 37) % 100],
                max_new_tokens=2)
        out = eng.generate_all()
        assert len(out[2]) == 2
        assert cache.stats["evicted"] >= 1


# -- admission queue + preempt-and-requeue -------------------------------


class TestAdmissionQueue:
    def test_put_queues_instead_of_raising(self, tiny):
        eng = make_engine(tiny, kv_blocks=13, max_blocks_per_seq=4)
        prompts = [(np.arange(20, dtype=np.int32) + i) % 100
                   for i in range(6)]
        # 6 x 3-block prompts into a 12-block pool: pre-PR-8 this raised
        eng.put(list(range(6)), prompts, max_new_tokens=4)
        assert eng.stats["queued"] == 6
        assert len(eng._queue) > 0  # backpressure, not an error
        out = eng.generate_all()
        assert sorted(out) == list(range(6))
        assert all(len(v) == 4 for v in out.values())
        # satellite: latency maps must be empty after a full drain
        assert eng._admit_time == {} and eng._last_emit_time == {}

    def test_never_fitting_prompt_rejected_up_front(self, tiny):
        eng = make_engine(tiny, max_blocks_per_seq=2)
        with pytest.raises(ValueError, match="never"):
            eng.put([1], [np.zeros(40, np.int32)])

    def test_max_queue_depth_backpressure(self, tiny):
        eng = make_engine(tiny, kv_blocks=13, max_blocks_per_seq=8,
                          max_queue_depth=1)
        eng.put([1], [np.arange(60, dtype=np.int32) % 100])  # 8 blocks
        assert len(eng.state.seqs) == 1
        eng.put([2], [np.arange(60, dtype=np.int32) % 100])  # queued
        assert len(eng._queue) == 1
        with pytest.raises(RuntimeError, match="queue full"):
            eng.put([3], [np.arange(60, dtype=np.int32) % 100])
        eng.flush([1, 2])
        assert not eng.state.seqs and not eng._queue

    def test_overload_preempts_requeues_and_drops_nothing(self, tiny):
        """KV-pool exhaustion mid-decode: victims requeue with their
        generated tokens and finish later; nothing is dropped and the
        overloaded output is bit-identical to an uncontended run."""
        prompts = [((np.arange(20) * 7 + i) % 100).astype(np.int32)
                   for i in range(6)]
        big = make_engine(tiny, kv_blocks=128, max_blocks_per_seq=4,
                          prefix_cache=False)
        big.put(list(range(6)), prompts, max_new_tokens=8)
        ref = big.generate_all()
        assert big.stats["preempted"] == 0

        eng = make_engine(tiny, kv_blocks=13, max_blocks_per_seq=4,
                          prefix_cache=False)
        eng.put(list(range(6)), prompts, max_new_tokens=8)
        out = eng.generate_all()
        # 4 admitted seqs all need a 4th block of an empty pool at once
        assert eng.stats["preempted"] >= 1
        assert eng.stats["requeued"] == eng.stats["preempted"]
        assert eng.stats["truncated"] == 0
        assert all(len(out[u]) == 8 for u in range(6))  # zero drops
        assert out == ref
        assert eng._admit_time == {} and eng._last_emit_time == {}

    @pytest.mark.slow  # two extra engine compiles; plain-overload +
    # prefix-reuse tests cover the tier-1 surface
    def test_overload_with_prefix_cache_matches_uncontended(self, tiny):
        """Preemption with the prefix cache ON: a victim's idle-cached
        blocks are either revived at readmission or evicted by the
        survivors — both must yield the uncontended token streams."""
        prompts = [((np.arange(20) * 3 + i) % 100).astype(np.int32)
                   for i in range(6)]
        big = make_engine(tiny, kv_blocks=128, max_blocks_per_seq=4)
        big.put(list(range(6)), prompts, max_new_tokens=8)
        ref = big.generate_all()
        eng = make_engine(tiny, kv_blocks=13, max_blocks_per_seq=4)
        eng.put(list(range(6)), prompts, max_new_tokens=8)
        out = eng.generate_all()
        assert eng.stats["preempted"] >= 1
        assert eng.stats["truncated"] == 0
        assert out == ref

    def test_requeued_victim_reattaches_own_cached_blocks(self):
        """StateManager level: a released sequence's registered prompt
        blocks go idle (not freed) and a requeue-shaped readmission
        (prompt + generated tokens) re-attaches them by content."""
        kv = _FakeKV(16, block_size=4)
        kv.prefix_cache = PrefixCache(4)
        state = StateManager(kv, max_blocks_per_seq=8)
        prompt = np.arange(10, dtype=np.int32)
        seq = state.get_or_create(1, prompt)
        assert state.ensure_capacity(seq, 10)
        seq.seen_tokens = 10
        state.register_prefix_blocks(seq)
        shared = [int(b) for b in seq.kv_blocks[:2]]
        state.release(1)
        assert kv.prefix_cache.evictable_blocks == 2
        # requeue shape: prompt + 3 already-generated tokens
        again = state.get_or_create(1, np.concatenate(
            [prompt, [7, 8, 9]]).astype(np.int32))
        assert state.attach_prefix(again) == 8
        assert [int(b) for b in again.kv_blocks] == shared
        assert again.seen_tokens == 8


# -- config block --------------------------------------------------------


class TestServingConfig:
    def test_defaults_and_overrides(self):
        from deepspeed_tpu.config.config import load_config

        cfg = load_config(None)
        assert cfg.serving.prefix_cache and not cfg.serving.spec_decode
        cfg = load_config({"serving": {"spec_decode": True, "spec_k": 2,
                                       "max_queue_depth": 8}})
        assert cfg.serving.spec_decode and cfg.serving.spec_k == 2
        assert cfg.serving.max_queue_depth == 8

    @pytest.mark.parametrize("bad", [{"spec_k": 0}, {"spec_ngram": -1},
                                     {"decode_steps": 0},
                                     {"max_queue_depth": 0}])
    def test_invalid_values_raise(self, bad):
        from deepspeed_tpu.config.config import load_config

        with pytest.raises(ValueError):
            load_config({"serving": bad})

    def test_engine_bridge(self, tiny):
        from deepspeed_tpu.config.config import load_config

        cfg = load_config({"serving": {
            "spec_decode": True, "spec_k": 2, "prefix_cache": False,
            "decode_steps": 3, "max_queue_depth": 5}})
        eng = make_engine(tiny, serving=cfg.serving)
        assert eng.spec_k == 2 and eng._drafter is not None
        assert eng.kv_cache.prefix_cache is None
        assert eng.decode_steps == 3 and eng._max_queue_depth == 5


# -- open-loop SLO harness -----------------------------------------------


def _tools_path():
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")


class TestSLOHarness:
    def test_slo_schema_smoke(self, monkeypatch):
        """serve_slo emits the full SLO schema on a CPU-sized run with
        zero dropped requests (tier-1 safe: 4 tiny requests, spec off)."""
        # off the chip the harness runs only as the CPU smoke, by name
        for k, v in (("BENCH_CPU_SMOKE", "1"),
                     ("SLO_REQUESTS", "4"), ("SLO_PROMPT", "24"),
                     ("SLO_SHARED_PREFIX", "16"), ("SLO_GEN", "4"),
                     ("SLO_RATE", "500"), ("SLO_SPEC", "0"),
                     ("SLO_COMPARE", "0")):
            monkeypatch.setenv(k, v)
        sys.path.insert(0, _tools_path())
        try:
            import serve_bench
            out = serve_bench.run_slo()
        finally:
            sys.path.remove(_tools_path())
        assert out["value"] > 0 and out["unit"] == "tokens/s"
        slo = out["slo"]
        assert slo["completed"] == 4 and slo["dropped"] == 0
        for key in ("ttft_p50_s", "ttft_p99_s", "decode_token_p50_s",
                    "decode_token_p99_s", "goodput_tokens_per_s",
                    "queue_depth_timeline", "prefill_tokens",
                    "prefix_hit_tokens", "preempted"):
            assert key in slo, key
        assert slo["ttft_p99_s"] >= slo["ttft_p50_s"] > 0
        assert isinstance(slo["queue_depth_timeline"], list)
        assert slo["prefix_hit_tokens"] > 0  # shared prefix workload

    @pytest.mark.slow
    def test_prefix_and_spec_speedup_vs_baseline(self, tiny):
        """Acceptance bar: >= 1.5x tokens/s on a shared-prefix +
        repetitive workload vs the no-spec/no-prefix-cache baseline
        (closed loop, both engines warmed so XLA compile and prefix-
        cache population happen outside the timed pass)."""
        rng = np.random.default_rng(0)
        shared = rng.integers(0, 255, 40).tolist()
        prompts = []
        for _ in range(12):
            motif = rng.integers(0, 255, 4).tolist()
            prompts.append(np.asarray(shared + motif + motif, np.int32))
        gen = 8

        def tokens_per_s(engine):
            # passes 1-2 warm XLA (the prefix-hit path batches different
            # bucket shapes than the cold pass) and populate the prefix
            # cache; pass 3 times the serving steady state
            for base_uid in (100, 200, 300):
                uids = [base_uid + i for i in range(12)]
                if base_uid == 300:
                    t0 = time.perf_counter()
                engine.put(uids, prompts, max_new_tokens=gen)
                out = engine.generate_all()
                assert sum(len(v) for v in out.values()) == 12 * gen
            return 12 * gen / (time.perf_counter() - t0)

        kw = dict(kv_blocks=129, kv_block_size=8, max_tokens_per_step=32,
                  max_seqs_per_step=16, max_blocks_per_seq=8,
                  decode_steps=4)
        opt = tokens_per_s(make_engine(
            tiny, prefix_cache=True, spec_decode=True, **kw))
        base = tokens_per_s(make_engine(
            tiny, prefix_cache=False, spec_decode=False, **kw))
        assert opt >= 1.5 * base, (opt, base)
