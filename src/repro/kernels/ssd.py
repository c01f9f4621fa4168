"""Mamba-2 SSD chunked scan (Pallas TPU).

Grid (B, H, num_chunks), chunks innermost ("arbitrary") carrying the
(P, N) SSM state in VMEM scratch. Per chunk the kernel does the
state-space-duality decomposition:

  intra-chunk: Y  = ((C B^T) .* L) (dt .* X)   — quadratic in chunk length,
                                                  all MXU matmuls
  inter-chunk: Y += (C h_in) with start-decay;  h_out = total_decay * h_in
                                                  + end-decayed B^T (dt X)

Chunk length 64–128 and N=128, P=64 give MXU-aligned contractions; the VMEM
working set is O(L*(P+2N) + P*N) floats per program (~0.2 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch


def _ssd_kernel(
    x_ref,    # (1, 1, L, P)
    dt_ref,   # (1, 1, L, 1)
    a_ref,    # (H,) SMEM
    b_ref,    # (1, 1, L, N)
    c_ref,    # (1, 1, L, N)
    h0_ref,   # (1, 1, P, N)
    y_ref,    # (1, 1, L, P)
    hl_ref,   # (1, 1, P, N)
    h_ref,    # scratch (P, N) f32
    *, num_chunks: int, chunk: int,
):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)    # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (L, 1)
    A = a_ref[hi]                           # scalar
    B = b_ref[0, 0].astype(jnp.float32)    # (L, N)
    C = c_ref[0, 0].astype(jnp.float32)    # (L, N)

    dA = dt * A                             # (L, 1) log-decay per step
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col                        # [l, m]: m <= l
    exact = dict(preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)
    # prefix sums as triangular matmuls (no cumsum or transpose in the
    # kernel): dA_cum[l] as a column, and dA_cum[m] broadcast along rows
    dA_cum = jax.lax.dot_general(           # (L, 1)
        tri.astype(jnp.float32), dA, (((1,), (0,)), ((), ())), **exact)
    dA_cum_m = jax.lax.dot_general(         # (L, L): [l, m] = dA_cum[m]
        jnp.ones((chunk, chunk), jnp.float32),
        jnp.where(row <= col, dA, 0.0), (((1,), (0,)), ((), ())), **exact)

    # intra-chunk decay matrix L[l, m] = exp(sum_{m<r<=l} dA_r), lower-tri
    Lmat = jnp.where(tri, jnp.exp(dA_cum - dA_cum_m), 0.0)

    scores = jax.lax.dot_general(            # C B^T: (L, L)
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    y = jax.lax.dot_general(                  # (L, P); dt scales x's rows
        scores * Lmat, x * dt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # inter-chunk: contribution of the state entering this chunk
    ch = jax.lax.dot_general(                 # C h_in: (L, P)
        C, h_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = y + jnp.exp(dA_cum) * ch
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h_out = total_decay * h_in + sum_l end_decay_l dt_l x_l B_l^T
    total = jnp.sum(dA, axis=0, keepdims=True)   # (1, 1)
    xw = x * (dt * jnp.exp(total - dA_cum))      # (L, P)
    hb = jax.lax.dot_general(                    # (P, N)
        xw, B, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h_ref[...] = jnp.exp(total) * h_ref[...] + hb

    @pl.when(ci == num_chunks - 1)
    def _final():
        hl_ref[0, 0] = h_ref[...].astype(hl_ref.dtype)


def ssd_bhcp(
    x: jax.Array,    # (B, H, S, P)
    dt: jax.Array,   # (B, H, S)
    A: jax.Array,    # (H,)
    Bm: jax.Array,   # (B, G, S, N)
    Cm: jax.Array,   # (B, G, S, N)
    h0: jax.Array,   # (B, H, P, N)
    *,
    chunk: int = 64,
    interpret: bool | None = None,
):
    b, h, s, p = x.shape
    g, n = Bm.shape[1], Bm.shape[3]
    rep = h // g
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, num_chunks=nc, chunk=chunk)
    y, hlast = launch.pallas_call(
        kernel,
        name="ssd",
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((h,), lambda bi, hi, ci: (0,), memory_space=launch.SMEM),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci, r=rep: (bi, hi // r, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci, r=rep: (bi, hi // r, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[launch.VMEM((p, n), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        rows=b * s,
    )(x, dt.reshape(b, h, s, 1), A.astype(jnp.float32), Bm, Cm, h0)
    return y, hlast
