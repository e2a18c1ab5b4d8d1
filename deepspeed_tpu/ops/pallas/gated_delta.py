"""The gated delta rule (gated DeltaNet, Yang et al. 2024,
arXiv:2412.06464): a head's state ``S`` (key x value, float32) and, for each
token,

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

Two forms of the same recurrence:

* :func:`gdn_decode` — one token for each sequence of a batch, as a Pallas
  kernel named ``gdn_decode``: a sequence's state is read from its slot of
  the pool once, decayed, updated, read out and written back into the same
  buffer (the pool is aliased to the output). Memory-bound: the state in and
  out, 4 MiB a sequence a layer at 32 heads of 128 x 128.
* :func:`gdn_chunk` — many tokens a sequence, in chunks (the WY form), in
  ``jax.numpy``: within a chunk the ``d_t`` solve one unit-triangular system,
  across chunks the state is carried. Exact to the recurrence up to
  rounding; every pairwise decay is formed as ``exp(G_i - G_j)`` with
  ``i >= j``, so nothing overflows however fast a head forgets.

Lightning attention (Qin et al. 2024, arXiv:2401.04658), the plain decayed
outer-product memory ``S <- a S + k_t v_t^T; o_t = S^T q_t`` with a decay that
is a constant of the head, lives on the same pool in the same two forms:
:func:`lightning_decode` (a Pallas kernel named ``lightning_decode``) and
:func:`lightning_chunk`.

The pool is ``state[layers, slots, heads, key_dim, value_dim]`` float32
(``inference/ragged/state_pool.py``); the kernel takes it whole with the
layer and the slots as scalar-prefetch operands, so a step program never
slices a layer or a slot out of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
# heads of one grid step of the decode kernel: 16 x 128 x 128 float32 is
# 1 MiB in and 1 MiB out, twice each for the pipeline's second buffer
HEADS_PER_BLOCK = 16
# tokens of one chunk of the chunked form
CHUNK = 64


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _column(row, eye):
    """A lane vector ``row [1, n]`` as a sublane column ``[n, 1]``: mask it
    onto the diagonal and sum the lanes (no transpose for Mosaic to refuse)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _decode_kernel(layer_ref, slot_ref, q_ref, k_ref, v_ref, dec_ref, beta_ref,
                   s_ref, o_ref, s_out_ref, *, heads: int):
    del layer_ref, slot_ref                  # used by the index maps
    dk = s_ref.shape[-2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    for h in range(heads):                   # static: one head's tile at a time
        row = slice(h, h + 1)
        S = s_ref[0, 0, h] * dec_ref[0, row, :]          # [dk, dv] * [1, dv]
        kcol = _column(k_ref[0, row, :], eye)            # [dk, 1]
        qcol = _column(q_ref[0, row, :], eye)
        kv = jnp.sum(S * kcol, axis=0, keepdims=True)    # [1, dv]
        d = beta_ref[0, row, :] * (v_ref[0, row, :] - kv)
        S = S + kcol * d
        o_ref[0, row, :] = jnp.sum(S * qcol, axis=0, keepdims=True)
        s_out_ref[0, 0, h] = S


def gdn_decode(state: jax.Array, layer, slots: jax.Array, q: jax.Array,
               k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array):
    """One token of the recurrence for each row of the batch, in place.

    state  [L, slots, n, dk, dv] float32 — the pool; donated by the caller's
           program, aliased to the result
    layer  int32 scalar (traced): the pool's layer
    slots  [B] int32: each row's slot (dead rows: the scratch slot)
    q, k   [B, n, dk] float32 (normalised; q scaled);  v [B, n, dv]
    g, beta [B, n] float32: log-decay (<= 0) and write strength

    Returns (o [B, n, dv] float32, state').
    """
    _, _, n, dk, dv = state.shape
    B = q.shape[0]
    if dk != dv:
        raise ValueError(f"gdn_decode takes square heads, got {dk} x {dv}")
    hb = min(HEADS_PER_BLOCK, n)
    while n % hb:
        hb -= 1
    # per-head scalars as lane vectors, so that every operand is lane-dense
    dec = jnp.broadcast_to(jnp.exp(g)[:, :, None], (B, n, dv))
    bet = jnp.broadcast_to(beta[:, :, None], (B, n, dv))

    def vec_spec():
        return pl.BlockSpec((1, hb, dk), lambda b, h, lyr, sl: (b, h, 0))

    def state_spec():
        return pl.BlockSpec((1, 1, hb, dk, dv),
                            lambda b, h, lyr, sl: (lyr[0], sl[b], h, 0, 0))

    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb),
        name="gdn_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n // hb),
            in_specs=[vec_spec(), vec_spec(), vec_spec(), vec_spec(),
                      vec_spec(), state_spec()],
            out_specs=[vec_spec(), state_spec()],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, n, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two scalar-prefetch arrays: the pool is the 8th
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      q, k, v, dec, bet, state)
    return o, state


def _solve_unit_lower(A: jax.Array, rhs: jax.Array):
    """``X`` with ``(I + A) X = rhs`` for strictly lower-triangular ``A
    [..., C, C]``, in float32 arithmetic whose precision is stated here
    (a backend's triangular solve may run its products in a lower one):
    the diagonal blocks are inverted by forward substitution, row by row,
    and the block rows are then solved in order with small products at
    ``highest``."""
    C = A.shape[-1]
    block = 16 if C % 16 == 0 else C
    nb = C // block
    lead = A.shape[:-2]
    Ab = A.reshape(lead + (nb, block, nb, block))
    Rb = rhs.reshape(lead + (nb, block, rhs.shape[-1]))
    diag = jnp.stack([Ab[..., r, :, r, :] for r in range(nb)], axis=-3)

    def row(i, T):      # T[i] = e_i - sum_{j<i} diag[i, j] T[j]
        d = jax.lax.dynamic_index_in_dim(diag, i, axis=-2, keepdims=False)
        upd = jnp.einsum("...j,...jk->...k", d, T, precision=HIGHEST)
        return jax.lax.dynamic_update_index_in_dim(
            T, jax.lax.dynamic_index_in_dim(T, i, axis=-2, keepdims=False)
            - upd, i, axis=-2)

    T = jax.lax.fori_loop(
        1, block, row,
        jnp.broadcast_to(jnp.eye(block, dtype=A.dtype), diag.shape))
    X = []
    for r in range(nb):
        acc = Rb[..., r, :, :]
        for s in range(r):
            acc = acc - jnp.einsum("...ij,...jk->...ik", Ab[..., r, :, s, :],
                                   X[s], precision=HIGHEST)
        X.append(jnp.einsum("...ij,...jk->...ik", T[..., r, :, :], acc,
                            precision=HIGHEST))
    return jnp.concatenate(X, axis=-2)


@jax.named_scope("gdn_chunk")
def gdn_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              beta: jax.Array, state: jax.Array, *, chunk: int = CHUNK):
    """The recurrence over ``T`` tokens of each of ``B`` sequences, in
    chunks.

    q, k [B, T, n, dk]; v [B, T, n, dv]; g, beta [B, T, n]; state [B, n, dk,
    dv] — all float32. A padding token carries ``beta = 0`` and ``g = 0``: it
    writes nothing and decays nothing. Returns (o [B, T, n, dv], state').

    Within a chunk, with ``G`` the running sum of ``g`` and ``S_0`` the state
    before it: ``d = W_v - W_k S_0`` where ``[W_v | W_k] = (I + A)^-1 [beta v |
    beta e^G k]`` and ``A_ij = beta_i e^(G_i - G_j) k_i . k_j`` below the
    diagonal; ``o = (e^G q) S_0 + (q k^T * D) d`` with ``D_ij = e^(G_i - G_j)``
    on and below it; ``S_C = e^(G_C) S_0 + (e^(G_C - G) k)^T d``.
    """
    B, T, n, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    pad = (-T) % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    N = (T + pad) // C

    def split(a):                  # [B, T, n, ...] -> [N, B, n, C, ...]
        a = a.reshape((B, N, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                                  # [N, B, n, C]
    i = jnp.arange(C)
    diff = G[..., :, None] - G[..., None, :]                    # G_i - G_j
    lower = i[:, None] >= i[None, :]
    D = jnp.exp(jnp.where(lower, diff, -jnp.inf))               # 0 above
    kk = jnp.einsum("...ik,...jk->...ij", k, k, precision=HIGHEST)
    A = beta[..., :, None] * kk * jnp.where(i[:, None] > i[None, :], D, 0.0)
    eG = jnp.exp(G)[..., None]
    rhs = jnp.concatenate([beta[..., None] * v, beta[..., None] * eG * k], -1)
    W = _solve_unit_lower(A, rhs)
    Wv, Wk = W[..., :dv], W[..., dv:]
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=HIGHEST) * D
    qg = q * eG
    kg = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])                                  # [N, B, n]

    def step(S, c):
        Wv_c, Wk_c, qk_c, qg_c, kg_c, last_c = c
        d = Wv_c - jnp.einsum("bnck,bnkv->bncv", Wk_c, S, precision=HIGHEST)
        o = (jnp.einsum("bnck,bnkv->bncv", qg_c, S, precision=HIGHEST)
             + jnp.einsum("bncj,bnjv->bncv", qk_c, d, precision=HIGHEST))
        S = (last_c[..., None, None] * S
             + jnp.einsum("bnck,bncv->bnkv", kg_c, d, precision=HIGHEST))
        return S, o

    state, o = jax.lax.scan(step, state, (Wv, Wk, qk, qg, kg, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # [B, N, C, n, dv]
    return o.reshape(B, N * C, n, dv)[:, :T], state


def _lightning_kernel(layer_ref, slot_ref, q_ref, k_ref, v_ref, dec_ref,
                      s_ref, o_ref, s_out_ref, *, heads: int):
    del layer_ref, slot_ref                  # used by the index maps
    dk = s_ref.shape[-2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    for h in range(heads):                   # static: one head's tile at a time
        row = slice(h, h + 1)
        S = (s_ref[0, 0, h] * dec_ref[0, row, :]
             + _column(k_ref[0, row, :], eye) * v_ref[0, row, :])
        o_ref[0, row, :] = jnp.sum(S * _column(q_ref[0, row, :], eye),
                                   axis=0, keepdims=True)
        s_out_ref[0, 0, h] = S


def lightning_decode(state: jax.Array, layer, slots: jax.Array, q: jax.Array,
                     k: jax.Array, v: jax.Array, decay: jax.Array):
    """One token of ``S <- a S + k v^T; o = S^T q`` for each row of the
    batch, in place on the pool (``gdn_decode``'s contract and layout).

    state  [L, slots, n, d, d] float32 — donated by the caller's program
    layer  int32 scalar (traced);  slots [B] int32 (dead rows: scratch)
    q, k, v [B, n, d] float32 (q already scaled)
    decay  [B, n] float32: the head's ``a`` (1 for a row that is to keep
           its state: a dead row writes ``k v^T`` into the scratch slot)

    Returns (o [B, n, d] float32, state').
    """
    _, _, n, dk, dv = state.shape
    B = q.shape[0]
    if dk != dv:
        raise ValueError(f"lightning_decode takes square heads, got {dk} x {dv}")
    hb = min(HEADS_PER_BLOCK, n)
    while n % hb:
        hb -= 1
    dec = jnp.broadcast_to(decay[:, :, None], (B, n, dv))

    def vec_spec():
        return pl.BlockSpec((1, hb, dk), lambda b, h, lyr, sl: (b, h, 0))

    def state_spec():
        return pl.BlockSpec((1, 1, hb, dk, dv),
                            lambda b, h, lyr, sl: (lyr[0], sl[b], h, 0, 0))

    o, state = pl.pallas_call(
        functools.partial(_lightning_kernel, heads=hb),
        name="lightning_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n // hb),
            in_specs=[vec_spec(), vec_spec(), vec_spec(), vec_spec(),
                      state_spec()],
            out_specs=[vec_spec(), state_spec()],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, n, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two scalar-prefetch arrays: the pool is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      q, k, v, dec, state)
    return o, state


@jax.named_scope("lightning_chunk")
def lightning_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                    state: jax.Array, *, chunk: int = CHUNK):
    """``S <- e^(g_t) S + k_t v_t^T; o_t = S^T q_t`` over ``T`` tokens of each
    of ``B`` sequences, in chunks.

    q, k [B, T, n, dk]; v [B, T, n, dv]; g [B, T, n] (the log-decay, <= 0; a
    padding token carries ``g = 0`` and ``k = 0``: it decays nothing and
    writes nothing); state [B, n, dk, dv] — all float32. Returns (o [B, T, n,
    dv], state').

    Within a chunk, with ``G`` the running sum of ``g`` and ``S_0`` the state
    before it: ``o = (e^G q) S_0 + ((q k^T) * D) v`` with ``D_ij = e^(G_i -
    G_j)`` on and below the diagonal, formed from the difference (a quotient
    of powers underflows for a head that forgets in a token); ``S_C = e^(G_C)
    S_0 + (e^(G_C - G) k)^T v``.
    """
    B, T, n, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    pad = (-T) % C
    if pad:
        q, k, v, g = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                      for a in (q, k, v, g))
    N = (T + pad) // C

    def split(a):                  # [B, T, n, ...] -> [N, B, n, C, ...]
        a = a.reshape((B, N, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v, g = map(split, (q, k, v, g))
    G = jnp.cumsum(g, axis=-1)                                  # [N, B, n, C]
    i = jnp.arange(C)
    D = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                          G[..., :, None] - G[..., None, :], -jnp.inf))
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=HIGHEST) * D
    intra = jnp.einsum("...ij,...jv->...iv", qk, v, precision=HIGHEST)
    qg = q * jnp.exp(G)[..., None]
    kg = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])                                  # [N, B, n]

    def step(S, c):
        intra_c, qg_c, kg_c, v_c, last_c = c
        o = intra_c + jnp.einsum("bnck,bnkv->bncv", qg_c, S, precision=HIGHEST)
        S = (last_c[..., None, None] * S
             + jnp.einsum("bnck,bncv->bnkv", kg_c, v_c, precision=HIGHEST))
        return S, o

    state, o = jax.lax.scan(step, state, (intra, qg, kg, v, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # [B, N, C, n, dv]
    return o.reshape(B, N * C, n, dv)[:, :T], state
