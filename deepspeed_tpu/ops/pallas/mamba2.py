"""The Mamba-2 state-space recurrence (Dao & Gu 2024, arXiv:2405.21060,
"SSD"): a head's state ``S`` (head dim x state, float32) and, for each token,

    S <- exp(dt_t A) S + dt_t x_t (x) B_t;   y_t = S C_t + D x_t

with a scalar decay a head (``A < 0``, the step size ``dt_t > 0``) and ``B_t``,
``C_t`` shared by the heads of a group. Beside the gated delta rule and
lightning attention of ``gated_delta.py`` this is the third recurrence of
the hybrid stack (``models/hybrid.py``, ``recurrent_kind`` "mamba2"), and the
first one the training step differentiates on the chip.

:func:`ssd_chunk` is the chunked form in ``jax.numpy`` that XLA compiles and
differentiates: within a chunk of ``C`` tokens the outputs are one masked
product, ``((C_i . B_j) e^(G_i - G_j) dt_j) x_j`` with ``G`` the running sum
of ``dt A`` (every pairwise decay formed from the difference, ``i >= j``, so
nothing overflows however fast a head forgets); a chunk leaves ``sum_j
e^(G_C - G_j) dt_j x_j (x) B_j`` behind; the states before each chunk are
those sums carried over the earlier chunks' whole decays, one small product
over the chunk axis (no loop); and ``e^(G_i) C_i`` reads the state before its
chunk. The products take their operands in the type of ``x`` and accumulate
in float32; step sizes, decays, their sums and the states are float32.
:func:`ssd_recurrence` is the recurrence as written, a scan over tokens, for
tests. No Pallas kernel yet: PERF.md, "what the system cannot run yet".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# tokens of one chunk (the ``nemotron_h`` family's ``chunk_size``)
CHUNK = 128


@jax.named_scope("ssd_chunk")
def ssd_chunk(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
              C: jax.Array, D: jax.Array, *, chunk: int = CHUNK):
    """The recurrence over ``T`` tokens of each of ``Bt`` sequences, every
    sequence from an empty state, in chunks of ``chunk``.

    x [Bt, T, n, P]; dt [Bt, T, n] float32 (> 0); A [n] float32 (< 0); B, C
    [Bt, T, G, N], head ``h`` reads group ``h // (n / G)``; D [n] float32.
    A length that is no whole number of chunks is padded with tokens of
    ``dt = 0``, which decay nothing and write nothing. Returns (y [Bt, T, n,
    P] float32, the state after the last token [Bt, n, P, N] float32).
    """
    Bt, T, n, P = x.shape
    G, N = B.shape[-2:]
    r, f32, dtype = n // G, jnp.float32, x.dtype
    pad = (-T) % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (T + pad) // chunk
    xc = x.reshape(Bt, nc, chunk, G, r, P)
    Bc, Cc = B.reshape(Bt, nc, chunk, G, N), C.reshape(Bt, nc, chunk, G, N)
    # [Bt, nc, G, r, chunk]: a token's step size, and the running sum of its
    # chunk's log-decays up to and with it
    dtc = jnp.moveaxis(dt.astype(f32).reshape(Bt, nc, chunk, G, r), 2, -1)
    Gs = jnp.cumsum(dtc * A.astype(f32).reshape(G, r, 1), axis=-1)

    # within a chunk: one masked product a head
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              Gs[..., :, None] - Gs[..., None, :], -jnp.inf))
    cb = jnp.einsum("bcigs,bcjgs->bcgij", Cc, Bc, preferred_element_type=f32)
    m = (cb[:, :, :, None] * decay * dtc[..., None, :]).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xc, preferred_element_type=f32)

    # what each chunk leaves behind, and its whole decay
    w = jnp.exp(Gs[..., -1:] - Gs) * dtc                    # [Bt,nc,G,r,chunk]
    xw = (xc * jnp.moveaxis(w, -1, 2)[..., None].astype(dtype))
    left = jnp.einsum("bcjgrp,bcjgs->bcgrps", xw, Bc,
                      preferred_element_type=f32)           # [Bt,nc,G,r,P,N]
    total = jnp.moveaxis(Gs[..., -1], 1, -1)                # [Bt, G, r, nc]
    # the state before chunk z (and, at z = nc, after the last): the earlier
    # chunks' sums, each decayed by the chunks between
    upto = jnp.cumsum(total, axis=-1)
    before = jnp.concatenate([jnp.zeros_like(upto[..., :1]), upto], axis=-1)
    z = jnp.arange(nc + 1)
    carry = jnp.exp(jnp.where(z[:, None] > z[None, :nc],
                              before[..., :, None] - upto[..., None, :],
                              -jnp.inf))                    # [Bt,G,r,nc+1,nc]
    states = jnp.einsum("bgrzc,bcgrps->bzgrps", carry, left,
                        precision=jax.lax.Precision.HIGHEST)
    # a token reads the state before its chunk, decayed up to itself
    off = jnp.einsum("bcigs,bcgrps->bcigrp", Cc, states[:, :nc].astype(dtype),
                     preferred_element_type=f32)
    y = y + off * jnp.moveaxis(jnp.exp(Gs), -1, 2)[..., None]
    y = y.reshape(Bt, nc * chunk, n, P)[:, :T]
    y = y + x[:, :T].astype(f32) * D.astype(f32)[:, None]
    return y, states[:, nc].reshape(Bt, n, P, N)


def ssd_recurrence(x, dt, A, B, C, D):
    """The same map as the recurrence is written, a token at a time, all
    float32 at ``highest``: what :func:`ssd_chunk` is tested against."""
    Bt, T, n, P = x.shape
    G, N = B.shape[-2:]
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    B, C = (jnp.repeat(a, n // G, axis=2) for a in (B, C))     # [Bt, T, n, N]

    def step(S, t):
        xt, dtt, Bt_, Ct = t
        S = (jnp.exp(dtt * A)[..., None, None] * S
             + jnp.einsum("bnp,bns->bnps", xt * dtt[..., None], Bt_,
                          precision=hi))
        return S, jnp.einsum("bnps,bns->bnp", S, Ct, precision=hi)

    S, y = jax.lax.scan(step, jnp.zeros((Bt, n, P, N), f32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1) + x * D.astype(f32)[:, None], S
