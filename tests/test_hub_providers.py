"""The hub's gauges are read, not pushed: an engine registers one method
(``MetricsHub.add_provider``) and every read of the hub calls it; and the
hub's compile counter counts a compilation once."""

import gc

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.observability.hub import MetricsHub, compile_stats
from deepspeed_tpu.observability.sinks import labeled_name
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh


class Source:
    def __init__(self, depth):
        self.depth, self.reads = depth, 0

    def gauges(self):
        self.reads += 1
        return {"serve.queue_depth": self.depth}

    def broken(self):
        raise KeyError("state changed under the reader")


def test_a_provider_is_evaluated_at_every_read_and_not_before():
    hub, src = MetricsHub(), Source(3)
    hub.gauge("train.loss", 1.5)
    hub.add_provider(src.gauges)
    assert src.reads == 0
    assert hub.snapshot()["gauges"] == {"train.loss": 1.5,
                                        "serve.queue_depth": 3.0}
    src.depth = 7                   # nobody told the hub
    assert hub.gauges["serve.queue_depth"] == 7.0
    assert "dstpu_serve_queue_depth 7" in hub.to_prometheus()
    assert src.reads == 3


def test_the_prometheus_sink_writes_what_the_providers_read(tmp_path):
    from deepspeed_tpu.observability.sinks import PrometheusTextSink

    hub, src = MetricsHub(), Source(5)
    hub._prom = PrometheusTextSink(str(tmp_path / "page.prom"))
    hub.add_provider(src.gauges, labels={"replica": "r0"})
    hub.write_prometheus()
    page = (tmp_path / "page.prom").read_text()
    assert 'dstpu_serve_queue_depth{replica="r0"} 5' in page


def test_two_sources_with_different_labels_keep_a_series_each():
    hub, a, b = MetricsHub(), Source(1), Source(2)
    hub.add_provider(a.gauges, labels={"replica": "r0"})
    hub.add_provider(b.gauges, labels={"replica": "r1"})
    assert hub.snapshot()["gauges"] == {
        'serve.queue_depth{replica="r0"}': 1.0,
        'serve.queue_depth{replica="r1"}': 2.0}


def test_a_dead_sources_provider_is_dropped_with_its_series():
    hub, a, b = MetricsHub(), Source(1), Source(2)
    hub.add_provider(a.gauges, labels={"replica": "r0"})
    hub.add_provider(b.gauges, labels={"replica": "r1"})
    del a
    gc.collect()
    assert hub.snapshot()["gauges"] == {'serve.queue_depth{replica="r1"}': 2.0}
    assert len(hub._providers) == 1


def test_a_failing_provider_costs_its_own_gauges_only():
    hub, good, bad = MetricsHub(), Source(4), Source(0)
    hub.add_provider(bad.broken)
    hub.add_provider(good.gauges)
    assert hub.snapshot()["gauges"] == {"serve.queue_depth": 4.0}


# -- the engine's gauges -------------------------------------------------------

F32 = jnp.float32
_MODEL = []


def _engine(label):
    if not _MODEL:
        m = get_model("tiny", param_dtype=F32, dtype=F32)
        _MODEL.append((m, m.init(jax.random.PRNGKey(0))))
    model, params = _MODEL[0]
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    return InferenceEngineV2(
        model, mesh=mesh, params=params, dtype=F32, kv_blocks=64,
        kv_block_size=8, max_tokens_per_step=16, max_seqs_per_step=2,
        max_blocks_per_seq=16, decode_steps=4,
        metric_labels={"replica": label})


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 200, n).astype(np.int32)


def _read(engine, name):
    return engine._hub.snapshot()["gauges"][
        labeled_name(name, engine._metric_labels)]


def test_the_documented_serve_gauges_read_what_the_eager_path_gave(devices):
    """Three requests into two slots, a prompt of two chunks among them:
    after each step every gauge docs/observability.md lists reads what
    ``_update_serve_gauges`` pushed at that step's end (the same
    expressions over the engine's state), and between steps too."""
    engine = _engine("a")
    engine.put([1, 2, 3], [_prompt(20, 1), _prompt(5, 2), _prompt(6, 3)],
               max_new_tokens=6)
    assert _read(engine, "serve.queue_wait_depth") == 1       # before a step
    for _ in range(4):
        engine.serve_step()
        live = [s for s in engine.state.seqs.values() if not s.done]
        st = engine.stats
        want = {
            "serve.queue_depth": len(live),
            "serve.queue_wait_depth": len(engine._queue),
            "serve.pending_prefill_tokens":
                sum(s.pending_prefill for s in live),
            "serve.kv_free_blocks": engine.kv_cache.free_blocks,
            "serve.prefix_cached_blocks":
                engine.kv_cache.prefix_cache.cached_blocks,
            "serve.batch_seq_occupancy":
                engine.scheduler.last_scheduled_seqs / 2,
            "serve.batch_token_occupancy":
                engine.scheduler.last_scheduled_tokens / 16,
            "serve.paged_fallback_ratio": 0.0}
        if st["calls_multi_decode"]:
            want["serve.burst_efficiency"] = \
                engine._burst_tokens / engine._burst_capacity
            want["serve.issued_ahead_share"] = \
                st["calls_issued_ahead"] / st["calls_multi_decode"]
        got = {k: _read(engine, k) for k in want}
        assert got == {k: float(v) for k, v in want.items()}
    assert engine.stats["calls_multi_decode"] >= 1
    assert "serve.burst_efficiency" in want
    engine.generate_all()
    assert _read(engine, "serve.queue_depth") == 0
    engine.close()


def test_two_engines_keep_their_own_series_and_a_dead_one_leaves(devices):
    a, b = _engine("a"), _engine("b")
    assert a._hub is b._hub
    a.put([1], [_prompt(5, 1)], max_new_tokens=3)
    assert _read(a, "serve.queue_depth") == 1
    assert _read(b, "serve.queue_depth") == 0
    mine = [k for k in a._hub.snapshot()["gauges"] if 'replica="a"' in k]
    assert 'serve.kv_free_blocks{replica="a"}' in mine
    a.generate_all()
    a.close()
    hub = a._hub
    del a
    gc.collect()
    after = hub.snapshot()["gauges"]
    assert not [k for k in after if 'replica="a"' in k and k in mine]
    assert 'serve.kv_free_blocks{replica="b"}' in after
    b.close()


# -- the compile counter -------------------------------------------------------

def test_one_fresh_jit_call_counts_one_compilation(devices):
    MetricsHub()                    # registers the listener, once a process
    x = jnp.arange(7.0)

    @jax.jit
    def fresh(v):
        return (v * 3.0 + 1.0).sum()

    before = compile_stats()
    fresh(x).block_until_ready()
    once = compile_stats()
    assert once["events"] - before["events"] == 1
    assert once["secs"] > before["secs"]
    fresh(x).block_until_ready()    # compiled: nothing more
    assert compile_stats() == once
