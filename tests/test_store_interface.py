"""The store interface (``inference/ragged/store.py``): what serving a new kind
of per-sequence state costs, and what the stores that exist refuse.

Read this first when you bring up an architecture whose layers keep something
new per sequence. The seam is: a ``Store`` class (here a toy one, in this
file), its spec from the runner's ``store_specs``, the programs that take its
arrays (the runner's), and its rows in the refusal matrix below. Nothing in
``inference/engine_v2.py``, ``inference/scheduler.py`` or
``inference/ragged/sequence.py`` changes.
"""

import dataclasses
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import engine_v2, model_runner
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.ragged import (
    OPS, LatentPoolUnsupported, PagedSession, PrefixCache,
    PooledKeysUnsupported, StateSnapshotUnsupported, Store,
    WindowedPoolUnsupported)
from deepspeed_tpu.models.zoo import get_model
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh
from deepspeed_tpu.serving import disagg

F32 = jnp.float32
PACKAGE = Path(deepspeed_tpu.__file__).parent


def _engine(model, params=None, **kw):
    mesh = build_mesh(TopologyConfig(), devices=jax.devices()[:1])
    args = dict(kv_blocks=64, kv_block_size=16, max_tokens_per_step=32,
                max_seqs_per_step=4, max_blocks_per_seq=8)
    args.update(kw)
    return InferenceEngineV2(model, mesh=mesh, params=params, dtype=F32,
                             **args)


# ---------------------------------------------------------------------------
# a store from outside the package serves through the engine
# ---------------------------------------------------------------------------


class ToyUnsupported(NotImplementedError):
    pass


@dataclasses.dataclass(frozen=True)
class ToySpec:
    slots: int

    def build(self):
        return ToyStore(self.slots)


class ToyStore(Store):
    """One int32 a slot, a slot a sequence: how many program calls carried
    the sequence. The last slot is scratch. Refuses migration."""

    name = "toy"
    unsupported = frozenset({"migration"})

    def __init__(self, slots: int):
        self.visits = jnp.zeros((slots + 1,), jnp.int32)
        self.free = list(range(slots))

    def error(self, what):
        return ToyUnsupported(f"{what} would leave the toy's count behind")

    def arrays(self):
        return {"toy": self.visits}

    def set_arrays(self, state):
        self.visits = state["toy"]

    def can_take(self):
        return bool(self.free)

    def take(self, seq):
        seq.held["toy"] = self.free.pop()
        self.visits = self.visits.at[seq.held["toy"]].set(0)

    def give_back(self, seq):
        self.free.append(seq.held.pop("toy"))

    def host_args(self, seqs, rows):
        slots = np.full(rows, len(self.visits) - 1, np.int32)
        for i, s in enumerate(seqs):
            slots[i] = s.held["toy"]
        return {"toy_slots": slots}

    def in_use(self):
        return {"toy_slots_in_use": len(self.visits) - 1 - len(self.free)}


class ToyRunner:
    """The dense runner, with the toy's store beside the pages and its
    arrays carried through the four programs (each call adds one to the
    slot of every row)."""

    def __getattr__(self, name):
        return getattr(model_runner, name)

    @staticmethod
    def store_specs(cfg, **sizes):
        paged, beside = model_runner.store_specs(cfg, **sizes)
        return paged, beside + [ToySpec(slots=2)]

    @staticmethod
    def _with_toy(program):
        def run(cfg, params, pools, *args, toy_slots, **kw):
            pools = dict(pools)
            toy = pools.pop("toy").at[toy_slots].add(1)
            out = program(cfg, params, pools, *args, **kw)
            return (out[0], dict(out[1], toy=toy), *out[2:])
        return run


for _name in ("ragged_forward", "ragged_prefill_forward",
              "ragged_decode_forward", "ragged_multi_decode"):
    setattr(ToyRunner, _name, staticmethod(
        ToyRunner._with_toy(getattr(model_runner, _name))))


def test_a_store_from_outside_the_package_serves_through_the_engine(
        monkeypatch):
    plain_model = get_model("tiny", dtype=F32, param_dtype=F32)
    params = plain_model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (40, 9, 21)]
    plain = _engine(plain_model, params)
    plain.put([1, 2, 3], prompts, max_new_tokens=12)
    want = plain.generate_all()
    plain.close()

    # a config of its own: compiled programs are shared by config identity
    model = get_model("tiny", dtype=F32, param_dtype=F32)
    monkeypatch.setattr(engine_v2, "runner_for", lambda cfg: ToyRunner())
    eng = _engine(model, params)
    toy = eng.kv_cache.store("toy")
    assert isinstance(toy, ToyStore) and eng.kv_cache.stores[-1] is eng.kv_cache
    assert set(eng.kv_cache.kv_state) == {"kv", "toy"}

    # two slots: the third request waits for one, as for a page
    eng.put([1, 2, 3], prompts, max_new_tokens=12)
    assert eng.stats["admitted"] == 2 and not toy.free
    assert not eng.can_schedule(4) and not eng.kv_cache.admissible()
    slots = {uid: s.held["toy"] for uid, s in eng.state.seqs.items()}
    assert sorted(slots.values()) == [0, 1]
    got = {1: [], 2: [], 3: []}
    for _ in range(3):              # the longer prompt's chunks, then the other's
        for uid, toks in eng.serve_step().items():
            got[uid] += toks
    visits = np.asarray(toy.visits)
    assert all(visits[slot] >= 1 for slot in slots.values())
    assert eng.kv_cache.occupancy()["toy_slots_in_use"] == 2

    # what the toy refuses, it refuses by its own name; the pages do not
    with pytest.raises(ToyUnsupported, match="migration"):
        eng.migrate_out_session(1)
    with pytest.raises(ToyUnsupported, match="migration"):
        eng.install_migrated_session(object())
    assert eng.kv_cache.supports("handoff")
    assert 1 in eng.state.seqs                       # nothing was released

    for uid, toks in eng.generate_all().items():
        got[uid] += toks
    assert got == want                               # and the tokens are the model's
    assert eng.stats["admitted"] == 3
    assert sorted(toy.free) == [0, 1] and not eng.state.seqs
    eng.close()
    # the engine was not taught the toy
    for path in ("inference/engine_v2.py", "inference/scheduler.py",
                 "inference/ragged/sequence.py"):
        assert "toy" not in (PACKAGE / path).read_text().lower()


# ---------------------------------------------------------------------------
# the refusals as one matrix
# ---------------------------------------------------------------------------

S, L, W, K = StateSnapshotUnsupported, LatentPoolUnsupported, \
    WindowedPoolUnsupported, PooledKeysUnsupported
# preset -> the error each operation raises (None: it is done, or for the
# prefix cache: it stays on). Where two stores refuse one operation the
# store beside the pages wins (``BlockedKVCache.stores``' order).
MATRIX = {
    "tiny": dict(host_tier=None, page_out=None, migrate_out=None,
                 migrate_in=None, handoff_serialize=None,
                 handoff_install=None, speculation=None, prefix_cache=None),
    # (a looped stack's pool has a slot a pass and layer: the stores move
    # ``[slots, blocks, ...]`` and do not ask what a slot is)
    "tiny-ouro": dict(host_tier=None, page_out=None, migrate_out=None,
                      migrate_in=None, handoff_serialize=None,
                      handoff_install=None, speculation=None,
                      prefix_cache=None),
    "tiny-hybrid": dict(host_tier=S, page_out=S, migrate_out=S, migrate_in=S,
                        handoff_serialize=S, handoff_install=S, speculation=S,
                        prefix_cache=S),
    "tiny-sala": dict(host_tier=S, page_out=S, migrate_out=S, migrate_in=S,
                      handoff_serialize=S, handoff_install=S, speculation=S,
                      prefix_cache=S),
    "tiny-kimi": dict(host_tier=None, page_out=None, migrate_out=L,
                      migrate_in=L, handoff_serialize=L, handoff_install=L,
                      speculation=L, prefix_cache=None),
    "tiny-dots3": dict(host_tier=W, page_out=W, migrate_out=W, migrate_in=W,
                       handoff_serialize=W, handoff_install=W, speculation=L,
                       prefix_cache=W),
    # (pooled keys are page-addressed: a shared whole page brings its row)
    "tiny-m3": dict(host_tier=K, page_out=K, migrate_out=K, migrate_in=K,
                    handoff_serialize=K, handoff_install=K, speculation=K,
                    prefix_cache=None),
}
# the operation as the stores know it (``store.OPS``)
OP_OF = dict(host_tier="host_tier", page_out="host_tier",
             migrate_out="migration", migrate_in="migration",
             handoff_serialize="handoff", handoff_install="handoff",
             speculation="speculation", prefix_cache="prefix_cache")
_BLOCK = {"tiny-dots3": 8, "tiny-m3": 8}
_MODELS, _ENGINES = {}, {}


def _preset_engine(preset, **kw):
    """One model a preset for the module; the plain engine of each too."""
    if preset not in _MODELS:
        _MODELS[preset] = get_model(preset, dtype=F32, param_dtype=F32)
    args = dict(kv_block_size=_BLOCK.get(preset, 16),
                max_blocks_per_seq=16, **kw)
    if kw:
        return _engine(_MODELS[preset], **args)
    if preset not in _ENGINES:
        _ENGINES[preset] = _engine(_MODELS[preset], **args)
    return _ENGINES[preset]


def _session(uid):
    return PagedSession(uid=uid, input_tokens=np.arange(5, dtype=np.int32),
                        generated=[], seen_tokens=0, max_new_tokens=2,
                        prior_generated=0, payload=None, scales=None)


@pytest.mark.parametrize("what", sorted(OP_OF))
@pytest.mark.parametrize("preset", sorted(MATRIX))
def test_refusal_matrix(preset, what):
    error = MATRIX[preset][what]
    eng = _preset_engine(preset)
    # what the stores declare is what the matrix pins
    refusing = [s for s in eng.kv_cache.stores
                if OP_OF[what] in s.unsupported]
    assert eng.kv_cache.supports(OP_OF[what]) == (error is None)
    assert (type(refusing[0].error("it")) if refusing else None) is error
    tokens = np.arange(40, dtype=np.int32)
    run = {
        "host_tier": lambda: _preset_engine(preset, host_kv_tier=True),
        "speculation": lambda: _preset_engine(preset, spec_decode=True),
        "page_out": lambda: eng.page_out(77),
        "migrate_out": lambda: eng.migrate_out_session(77),
        "migrate_in": lambda: eng.install_migrated_session(_session(78)),
        "handoff_serialize": lambda: disagg.serialize_prefix(eng, tokens),
        "handoff_install": lambda: disagg.install_prefix(eng, None),
    }
    if what == "prefix_cache":
        # asked for (the default), and switched off where a store refuses
        assert (eng.kv_cache.prefix_cache is None) == (error is not None)
        if error is not None:
            # attached behind the engine's back: the manager refuses the hit
            eng.kv_cache.prefix_cache = PrefixCache(16)
            seq = eng.state.get_or_create(79, tokens)
            with pytest.raises(error, match="prefix cache"):
                eng.state.attach_prefix(seq)
            eng.state.release(79)
            eng.kv_cache.prefix_cache = None
        return
    if error is not None:
        with pytest.raises(error):
            run[what]()
        return
    got = run[what]()
    if what == "host_tier":
        assert got.kv_cache.host_tier is not None
    elif what == "speculation":
        assert got._drafter is not None
    elif what == "migrate_in":
        assert got == "recompute" and 78 in eng.state.seqs
        eng.flush([78])
    else:       # nothing parked, live or cached under these names
        assert got in (False, None, (0, 0))
    assert not eng.state.seqs and not eng._queue


def test_every_refusal_is_of_a_known_operation():
    eng = _preset_engine("tiny-dots3")
    assert set(OP_OF.values()) == set(OPS)
    for store in eng.kv_cache.stores:
        assert store.unsupported <= set(OPS)
    with pytest.raises(AssertionError):
        eng.kv_cache.require("teleport", "it")


# ---------------------------------------------------------------------------
# census: the engine asks the cache and the runner, not the model
# ---------------------------------------------------------------------------

_STATE_ATTRIBUTES = ("recurrent_layers", "latent_dim", "window_latent_dim",
                     "sparse", "stack_plan", "index_key_dim", "msa")


def test_engine_scheduler_and_sequences_name_no_kind_of_state():
    for path in ("inference/engine_v2.py", "inference/scheduler.py",
                 "inference/ragged/sequence.py"):
        text = (PACKAGE / path).read_text()
        for attr in _STATE_ATTRIBUTES:
            assert not re.search(
                r"(getattr|hasattr)\([^()]*['\"]" + attr + r"['\"]", text), \
                (path, attr)
            assert not re.search(r"\bcfg\." + attr + r"\b", text), (path, attr)
        assert not re.search(r"\b(state_pool|window_pool)\b", text), path
    for path in ("inference/engine_v2.py", "serving/disagg.py",
                 "inference/ragged/kv_cache.py"):
        assert not re.search(
            r"self\._(recurrent|hybrid|latent|windowed|sparse|no_gather)\b"
            r"|_refuse_|pools_as_dict", (PACKAGE / path).read_text()), path
