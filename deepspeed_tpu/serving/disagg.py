"""Prefill/decode disaggregation: the KV-block handoff codec.

Disaggregated serving (DistServe, Splitwise; PAPERS.md) runs prefill and
decode on different replicas so a long-prompt burst never sits in front
of another request's next token — decode p99 is isolated by placement,
not by scheduling heroics. The hard part is moving the prompt's KV from
the prefill replica to the decode replica. Here the transport is the
prefix cache's own vocabulary:

* ``serialize_prefix`` — after a prefill replica finishes a request's
  first token, its full, write-complete prompt blocks are already
  registered in that replica's prefix cache under a content-hash chain
  (``ragged/prefix_cache.py``). Serialization is a lookup of that chain
  plus one host copy of the block contents — no new wire format, the
  chain keys ARE the codec.
* ``install_prefix`` — the decode replica allocates blocks, writes the
  payload into its own KV pool, and registers the same chain keys as
  *idle* cache entries. When the router then resubmits
  ``prompt + [first_token]`` to the decode replica, the ordinary
  ``StateManager.attach_prefix`` path revives the chain by content hash
  and the decode replica skips re-prefilling everything the payload
  covered — the handoff needs no special admission path at all.

Greedy bit-identity is preserved by construction: KV content for a
token depends only on the tokens before it and the (shared) params, so
installed blocks are exactly what the decode replica would have
computed; the partial tail block is recomputed locally like any other
prefix-cache hit. Every degradation (no cache, geometry mismatch, pool
too full) returns a zero-block install and the decode replica simply
prefills from scratch — disaggregation can lose its optimization but
never a request.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import numpy as np

# handoff wire codec modes (engine `handoff_wire` knob / autotuner axis):
#   auto — ship the source pool's native format (quantized pool: int8
#          payload + scales as-is; bf16 pool: raw)
#   raw  — full-precision bf16 blocks (pre-quant wire format)
#   int8 — bf16 pools quantize per head vector for the wire (~0.53x)
#   int4 — as int8, then two nibbles pack per byte (~0.28x — the
#          <=0.35x-of-bf16 acceptance mode)
#   fp8  — e4m3 payload + per-vector scales (~0.53x, the quality
#          midpoint between int8 and int4) shipped NATIVELY — no bf16
#          round trip; matches the PR 17 fp8 KV pool rung
WIRE_MODES = ("auto", "raw", "int8", "int4", "fp8")


@dataclasses.dataclass
class KVHandoff:
    """Serialized write-complete prompt blocks from one replica's pool.

    ``block_data`` is host memory shaped
    ``[num_layers, n_blocks, block_size, 2, kv_heads, head_dim]`` —
    the pool layout of the covered blocks, in chain order (for the int4
    wire the last dim is ``head_dim/2`` packed bytes and ``packed`` is
    set). ``keys`` is the content-hash chain that addresses them on any
    replica. ``scales`` rides along for quantized wires: one fp32 per
    (layer, block, row, k/v, head) vector. ``src_quant_bits`` records
    the SOURCE pool's storage mode so the installer can warn on a
    fleet-wide precision mismatch (quantized pool feeding a bf16 pool
    or vice versa — silent double conversion)."""

    keys: List[str]
    block_data: np.ndarray
    block_size: int
    scales: Optional[np.ndarray] = None
    wire_bits: Optional[Any] = None   # None = full precision; 4/8/"fp8"
    packed: bool = False              # int4 nibble packing along head_dim
    src_quant_bits: Optional[Any] = None
    wire_snr_db: Optional[float] = None  # measured at wire-quantize time

    @property
    def n_blocks(self) -> int:
        return len(self.keys)

    @property
    def n_tokens(self) -> int:
        return len(self.keys) * self.block_size

    @property
    def head_dim(self) -> int:
        hd = self.block_data.shape[-1]
        return hd * 2 if self.packed else hd

    @property
    def wire_nbytes(self) -> int:
        """Bytes this payload actually puts on the wire."""
        n = int(self.block_data.nbytes)
        if self.scales is not None:
            n += int(self.scales.nbytes)
        return n

    @property
    def logical_nbytes(self) -> int:
        """Full-precision bytes of the same blocks — the pre-quant wire
        format (a raw handoff IS full precision; quantized wires compare
        against the bf16 serving pool)."""
        if self.wire_bits is None:
            return int(self.block_data.nbytes)
        return int(np.prod(self.block_data.shape[:-1])) * self.head_dim * 2


def _record_wire(engine, handoff: KVHandoff, where: str) -> None:
    """Wire-vs-logical byte accounting for one handoff: hub counters,
    a comm traced_span (flight ring + Perfetto comm lane), and — when
    quant.* collection is configured — a published kv_wire region."""
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.observability import quant_stats

    with comm.traced_span("kv_handoff", handoff.block_data, "host",
                          f"kv_handoff_{where}"):
        pass
    # per-engine accumulators feed replica.load_report → fleet snapshot
    engine._handoff_wire_bytes = (
        getattr(engine, "_handoff_wire_bytes", 0) + handoff.wire_nbytes)
    engine._handoff_logical_bytes = (
        getattr(engine, "_handoff_logical_bytes", 0)
        + handoff.logical_nbytes)
    if handoff.wire_snr_db is not None:
        engine._last_kv_wire_snr_db = handoff.wire_snr_db
    hub = getattr(engine, "_hub", None)
    if hub is not None:
        lbl = getattr(engine, "_metric_labels", None)
        hub.counter_add("serve.handoff_wire_bytes", handoff.wire_nbytes,
                        labels=lbl)
        hub.counter_add("serve.handoff_logical_bytes",
                        handoff.logical_nbytes, labels=lbl)
        if handoff.wire_bits is not None:
            hub.gauge("quant.kv_wire.compression",
                      handoff.logical_nbytes / max(1, handoff.wire_nbytes),
                      labels=lbl)
    if quant_stats.collection_configured() and handoff.wire_bits is not None:
        st = quant_stats.QuantRegionStats(
            region="kv_wire", snr_db=handoff.wire_snr_db, max_rel_err=0.0,
            logical_bytes=handoff.logical_nbytes,
            wire_bytes=handoff.wire_nbytes,
            n_elements=int(np.prod(handoff.block_data.shape[:-1]))
            * handoff.head_dim,
            bits=handoff.wire_bits, block=handoff.head_dim,
            note=f"disagg handoff {where}: {handoff.n_blocks} blocks")
        quant_stats.publish([st], hub=hub)


def _wire_quantize(data: np.ndarray, scales: Optional[np.ndarray],
                   src_bits, wire: str):
    """Wire-side quantization for bf16 pools: convert ``data`` (+
    ``scales``) to the requested wire codec. A quantized pool ships its
    native payload untouched (its bf16 original no longer exists), so
    the conversion applies only when ``src_bits`` is None. Returns
    ``(data, scales, wire_bits, packed, wire_snr_db)`` — the SNR is
    measured HERE, the one place the full-precision original and the
    wire payload coexist."""
    # an int4 pool's native payload is already nibble-packed — mark it
    # so head_dim geometry and the installer's unpack stay correct
    wire_bits, packed, wire_snr = src_bits, src_bits == 4, None
    if src_bits is None and wire in ("int8", "int4", "fp8"):
        import jax.numpy as jnp

        from deepspeed_tpu.ops.pallas.quantization import (kv_dequantize,
                                                           kv_quantize,
                                                           pack_int4)

        bits = {"int8": 8, "int4": 4, "fp8": "fp8"}[wire]
        if bits == 4 and data.shape[-1] % 2:
            bits = 8  # nibble packing needs an even head_dim
        q, s = kv_quantize(jnp.asarray(data), bits=bits)
        err = (np.asarray(kv_dequantize(q, s, dtype=jnp.float32),
                          np.float32) - np.asarray(data, np.float32))
        sig = float(np.sum(np.asarray(data, np.float32) ** 2))
        noise = float(np.sum(err ** 2))
        wire_snr = (float("inf") if noise == 0.0
                    else 10.0 * float(np.log10(max(sig, 1e-30) / noise)))
        if bits == 4:
            q = pack_int4(q)
            packed = True
        data, scales, wire_bits = np.asarray(q), np.asarray(s), bits
    return data, scales, wire_bits, packed, wire_snr


def _pool_convert(kvc, payload, ssel, wire_bits, packed: bool):
    """Convert a wire payload (+ scales) into ``kvc``'s pool-native
    storage: the install-side half of the codec, shared by
    ``install_prefix`` and ``install_session``. ``payload``/``ssel``
    are jnp arrays (scales fp32 or None); returns ``(q, s)`` with ``q``
    in the pool dtype (nibble-packed when the pool is int4) and ``s``
    the fp32 scales or None for a bf16 pool."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.quantization import (kv_dequantize,
                                                       kv_quantize,
                                                       pack_int4,
                                                       unpack_int4)

    dst_bits = getattr(kvc, "quant_bits", None)
    if packed:
        payload = unpack_int4(payload)
    if dst_bits is not None:
        if wire_bits is None:
            # raw bf16 wire into a quantized pool: quantize-on-install
            q, s = kv_quantize(payload, bits=dst_bits)
        elif wire_bits == dst_bits:
            # wire already in the pool's own format: install directly
            q = payload if dst_bits == "fp8" else payload.astype(jnp.int8)
            s = ssel
        elif dst_bits == "fp8" or wire_bits == "fp8":
            # int<->fp8: the stored codes don't reinterpret (int grids
            # are scale*code on an integer lattice, e4m3 is a float
            # format), so round-trip through f32 onto the destination's
            # grid (the precision-mismatch warn above fired)
            q, s = kv_quantize(
                kv_dequantize(payload, ssel, dtype=jnp.float32),
                bits=dst_bits)
        elif dst_bits == 4 and wire_bits == 8:
            # int8 wire values overflow the int4 grid: requantize on the
            # coarser grid (the precision-mismatch warn above fired)
            q, s = kv_quantize(
                kv_dequantize(payload, ssel, dtype=jnp.float32), bits=4)
        else:
            # int4 values install into an int8 pool directly — dequant
            # is q*s either way, just on a coarser grid
            q, s = payload.astype(jnp.int8), ssel
        if dst_bits == 4:
            q = pack_int4(q.astype(jnp.int8))
        return q.astype(kvc.data.dtype), s
    if wire_bits is None:
        return payload.astype(kvc.data.dtype), None
    return kv_dequantize(payload, ssel, dtype=kvc.data.dtype), None


# what the hand-off is called where a store refuses it (``kv_cache.require``:
# prefix blocks without a recurrent state or a ring's rows are a wrong answer
# on the target, and the codec is laid out by K/V head: a latent pool has none)
_HANDOFF = "the disagg prefill->decode hand-off wire"


def serialize_prefix(engine, tokens,
                     max_blocks: Optional[int] = None,
                     wire: Optional[str] = None
                     ) -> Optional[KVHandoff]:
    """Serialize the cached full-block chain covering ``tokens`` from
    ``engine``'s KV pool. Returns None when nothing is cached (short
    prompt, prefix cache off, or the chain was already evicted) — the
    caller then hands off tokens only and the target recomputes.

    ``wire`` picks the codec (:data:`WIRE_MODES`; default the engine's
    ``handoff_wire`` knob). A quantized pool always ships its native
    int8 payload + scales as-is — its bf16 original no longer exists —
    so ``wire`` only selects a conversion for bf16 pools.

    The chain is ref'd for the duration of the device→host copy so KV
    pressure on the source replica cannot evict-and-recycle a block
    mid-serialization."""
    engine.kv_cache.require("handoff", _HANDOFF)
    cache = getattr(engine.kv_cache, "prefix_cache", None)
    if cache is None:
        return None
    wire = wire or getattr(engine, "_handoff_wire", "auto") or "auto"
    if wire not in WIRE_MODES:
        raise ValueError(f"handoff wire mode {wire!r} "
                         f"(choose from {WIRE_MODES})")
    toks = np.asarray(tokens, np.int32).ravel()
    # same cap as attach_prefix: the final prompt token stays uncached
    # so admission still computes first-token logits
    keys, blocks = cache.lookup(toks, max_tokens=len(toks) - 1)
    if not keys:
        return None
    if max_blocks is not None:
        keys, blocks = keys[:max_blocks], blocks[:max_blocks]
    kvc = engine.kv_cache
    src_bits = getattr(kvc, "quant_bits", None)
    cache.ref(keys)
    try:
        data, scales = kvc.read_blocks_host(blocks)
    finally:
        cache.unref(keys)
    data, scales, wire_bits, packed, wire_snr = _wire_quantize(
        data, scales, src_bits, wire)
    handoff = KVHandoff(keys=keys, block_data=data,
                        block_size=cache.block_size, scales=scales,
                        wire_bits=wire_bits, packed=packed,
                        src_quant_bits=src_bits, wire_snr_db=wire_snr)
    _record_wire(engine, handoff, "serialize")
    return handoff


def install_prefix(engine, handoff: Optional[KVHandoff]
                   ) -> Tuple[int, int]:
    """Install a handoff payload into ``engine``'s pool + prefix cache.

    Returns ``(blocks_installed, tokens_attachable)`` where the token
    count covers the whole chain the target now holds (payload blocks
    plus any chain prefix it already cached from earlier traffic). A
    ``(0, 0)`` return means the handoff degraded to recompute — never
    an error.

    Must run on the thread that owns ``engine`` (the replica pump): it
    mutates the pool array and the cache registry."""
    engine.kv_cache.require("handoff", _HANDOFF)
    cache = getattr(engine.kv_cache, "prefix_cache", None)
    if cache is None or handoff is None or not handoff.keys:
        return (0, 0)
    kvc = engine.kv_cache
    dst_bits = getattr(kvc, "quant_bits", None)
    # geometry on the LOGICAL layout — an int4-packed payload halves the
    # stored head_dim, a quantized destination pool is int8 either way
    if (handoff.block_size != cache.block_size
            or handoff.block_data.shape[0] != kvc.data.shape[0]
            or handoff.block_data.shape[2:5] != kvc.data.shape[2:5]
            or handoff.head_dim != kvc.config.head_dim):
        return (0, 0)  # geometry mismatch: heterogeneous fleet, recompute
    if handoff.src_quant_bits != dst_bits:
        from deepspeed_tpu.observability.quant_stats import warn_once

        warn_once(
            f"handoff_precision:{handoff.src_quant_bits}->{dst_bits}",
            "disagg handoff precision mismatch: source pool "
            f"quant_bits={handoff.src_quant_bits} feeding destination "
            f"quant_bits={dst_bits} — every transfer pays a "
            "quantize/dequantize conversion on install; align "
            "kv_quant_bits across the fleet (or set handoff_wire) to "
            "make the wire format match the pools")
    # the target may already hold a chain prefix (shared system prompt
    # traffic): install only past the longest cached prefix — suffix
    # keys without their predecessors would be unreachable by lookup
    pos = 0
    while pos < len(handoff.keys) and cache.get(handoff.keys[pos]) is not None:
        pos += 1
    to_install = list(range(pos, len(handoff.keys)))
    if not to_install:
        return (0, handoff.n_tokens)
    need = len(to_install)
    if kvc.free_blocks < need:
        kvc.reclaim(need - kvc.free_blocks)
    if kvc.free_blocks < need:
        # pool under live pressure: installing would evict working-set
        # blocks of running decodes — degrade to recompute instead
        return (0, pos * handoff.block_size)

    import jax.numpy as jnp

    blocks = kvc.allocator.allocate(need)
    sel = handoff.block_data[:, to_install]
    ssel = (None if handoff.scales is None
            else jnp.asarray(handoff.scales[:, to_install], jnp.float32))
    q, s = _pool_convert(kvc, jnp.asarray(sel), ssel,
                         handoff.wire_bits, handoff.packed)
    kvc.write_blocks(blocks, q, s)
    installed: List[str] = []
    for idx, blk in zip(to_install, blocks):
        if cache.register(handoff.keys[idx], int(blk)):
            installed.append(handoff.keys[idx])
        else:  # registered concurrently under another block: keep theirs
            kvc.free([int(blk)])
    # drop the registration ref: the chain parks idle-cached, exactly
    # like a released prompt — attach_prefix revives it by content hash
    # and KV pressure can evict it, so an unused handoff costs nothing
    cache.unref(installed)
    hub = getattr(engine, "_hub", None)
    if hub is not None and installed:
        lbl = getattr(engine, "_metric_labels", None)
        hub.counter_add("serve.handoff_blocks", len(installed), labels=lbl)
        hub.counter_add("serve.handoff_tokens",
                        len(installed) * handoff.block_size, labels=lbl)
    if installed:
        _record_wire(engine, handoff, "install")
    return (len(installed), handoff.n_tokens)


# -- live session migration (ISSUE 20) -----------------------------------


@dataclasses.dataclass
class SessionHandoff:
    """A full mid-stream decode session on the wire: the committed KV
    blocks (partial tail block included) in the same codec as
    :class:`KVHandoff`, plus the descriptor state that resumes decode on
    the target — generated tokens, budgets, and the per-request
    spec-acceptance EWMA. Unlike a prefix handoff there is no chain-key
    addressing: the blocks belong to ONE sequence and install by block
    write, not cache registration."""

    uid: int
    input_tokens: np.ndarray
    generated: List[int]
    seen_tokens: int
    max_new_tokens: int
    prior_generated: int
    block_data: np.ndarray            # [L, n_blocks, bs, 2, H, W]
    block_size: int
    scales: Optional[np.ndarray] = None
    wire_bits: Optional[Any] = None   # None = full precision; 4/8/"fp8"
    packed: bool = False              # int4 nibble packing along head_dim
    src_quant_bits: Optional[Any] = None
    wire_snr_db: Optional[float] = None
    spec_accept_ewma: Optional[float] = None

    @property
    def n_blocks(self) -> int:
        return int(self.block_data.shape[1])

    @property
    def head_dim(self) -> int:
        hd = self.block_data.shape[-1]
        return hd * 2 if self.packed else hd

    @property
    def wire_nbytes(self) -> int:
        n = int(self.block_data.nbytes)
        if self.scales is not None:
            n += int(self.scales.nbytes)
        return n

    @property
    def logical_nbytes(self) -> int:
        if self.wire_bits is None:
            return int(self.block_data.nbytes)
        return int(np.prod(self.block_data.shape[:-1])) * self.head_dim * 2


def serialize_session(engine, uid: int,
                      wire: Optional[str] = None
                      ) -> Optional[SessionHandoff]:
    """Destructively capture ``uid``'s live decode state from ``engine``
    for migration (engine.migrate_out_session owns the capture: the
    sequence — or its host-tier parked copy — is RELEASED). The KV
    payload rides the same quantized wire as a prefix handoff (``wire``
    from :data:`WIRE_MODES`, defaulting to the engine's ``handoff_wire``
    knob; a quantized pool ships its native payload as-is). Returns None
    when nothing warm exists to capture — the caller degrades to the
    legacy fold-and-resubmit recompute path."""
    wire = wire or getattr(engine, "_handoff_wire", "auto") or "auto"
    if wire not in WIRE_MODES:
        raise ValueError(f"handoff wire mode {wire!r} "
                         f"(choose from {WIRE_MODES})")
    cap = engine.migrate_out_session(uid)
    if cap is None:
        return None
    src_bits = getattr(engine.kv_cache, "quant_bits", None)
    data, scales, wire_bits, packed, wire_snr = _wire_quantize(
        cap["payload"], cap["scales"], src_bits, wire)
    sess = SessionHandoff(
        uid=cap["uid"], input_tokens=cap["input_tokens"],
        generated=cap["generated"], seen_tokens=cap["seen_tokens"],
        max_new_tokens=cap["max_new_tokens"],
        prior_generated=cap["prior_generated"],
        block_data=data, block_size=engine.kv_cache.config.block_size,
        scales=scales, wire_bits=wire_bits, packed=packed,
        src_quant_bits=src_bits, wire_snr_db=wire_snr,
        spec_accept_ewma=cap["spec_accept_ewma"])
    _record_wire(engine, sess, "serialize_session")
    return sess


def install_session(engine, sess: Optional[SessionHandoff]) -> str:
    """Install a migrated session into ``engine`` and resume it. The
    graceful-degradation ladder (never an error, never a drop):

    * ``"resumed"``   — warm: blocks converted to the pool's native
      format and written; decode continues with zero re-prefill FLOPs;
    * ``"paged"``     — target HBM full: warm bytes park in the host
      tier, readmission warm-resumes later (still zero re-prefill);
    * ``"recompute"`` — geometry mismatch / unknown wire / no payload /
      no tier room: the folded token history queues for ordinary
      prefix-recompute admission;
    * ``"duplicate"`` / ``"truncated"`` — see
      ``engine.install_migrated_session``.

    Must run on the thread that owns ``engine`` (the replica pump)."""
    if sess is None:
        return "recompute"
    from deepspeed_tpu.inference.ragged.kv_tier import PagedSession

    kvc = engine.kv_cache
    dst_bits = getattr(kvc, "quant_bits", None)
    pool_payload = pool_scales = None
    geometry_ok = (
        sess.block_data is not None and sess.n_blocks > 0
        and sess.block_size == kvc.config.block_size
        and sess.block_data.shape[0] == kvc.data.shape[0]
        and sess.block_data.shape[2:5] == kvc.data.shape[2:5]
        and sess.head_dim == kvc.config.head_dim
        and sess.wire_bits in (None, 4, 8, "fp8"))
    if geometry_ok:
        if sess.src_quant_bits != dst_bits:
            from deepspeed_tpu.observability.quant_stats import warn_once

            warn_once(
                f"handoff_precision:{sess.src_quant_bits}->{dst_bits}",
                "disagg handoff precision mismatch: source pool "
                f"quant_bits={sess.src_quant_bits} feeding destination "
                f"quant_bits={dst_bits} — every transfer pays a "
                "quantize/dequantize conversion on install; align "
                "kv_quant_bits across the fleet (or set handoff_wire) "
                "to make the wire format match the pools")
        import jax.numpy as jnp

        q, s = _pool_convert(
            kvc, jnp.asarray(sess.block_data),
            None if sess.scales is None
            else jnp.asarray(sess.scales, jnp.float32),
            sess.wire_bits, sess.packed)
        pool_payload = np.asarray(q)
        pool_scales = None if s is None else np.asarray(s, np.float32)
    paged = PagedSession(
        uid=sess.uid,
        input_tokens=np.asarray(sess.input_tokens, np.int32),
        generated=list(sess.generated),
        seen_tokens=int(sess.seen_tokens),
        max_new_tokens=int(sess.max_new_tokens),
        prior_generated=int(sess.prior_generated),
        payload=pool_payload, scales=pool_scales,
        spec_accept_ewma=sess.spec_accept_ewma)
    rung = engine.install_migrated_session(paged)
    if rung in ("resumed", "paged"):
        _record_wire(engine, sess, "install_session")
    return rung
