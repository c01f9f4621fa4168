"""RG-LRU linear recurrence (Pallas TPU) — recurrentgemma / Griffin.

h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (sigmoid(i_t) * x_t),
a_t = exp(-c * softplus(a_param) * sigmoid(r_t)).

Grid (B, num_width_blocks, num_seq_chunks): the time dimension is innermost
("arbitrary") carrying the hidden state in VMEM scratch across chunks, so
sequence length is unbounded by VMEM. Within a chunk the linear recurrence is
a log-depth Hillis-Steele scan along the sublane axis (``launch.roll`` plus
an iota mask per doubling step, fully vectorized on the VPU; no per-timestep
scalar loop): composing (a, b) |-> h -> a*h + b gives
h_t = Acum_t * h_chunk_start + Bcum_t.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch


def _linear_scan(a, b):
    """Inclusive prefix composition of h -> a*h + b along axis 0."""
    n = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    k = 1
    while k < n:
        valid = row >= k                 # rows t >= k combine with t - k
        a_prev = launch.roll(a, k, 0)    # a_prev[t] = a[t - k]
        b_prev = launch.roll(b, k, 0)
        b = jnp.where(valid, a * b_prev + b, b)
        a = jnp.where(valid, a * a_prev, a)
        k *= 2
    return a, b


def _rglru_kernel(
    x_ref, r_ref, i_ref, a_ref, h0_ref, o_ref, hlast_ref, h_ref,
    *, c: float, block_s: int, num_seq_chunks: int,
):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)   # (Bs, Bw)
    r = r_ref[0].astype(jnp.float32)
    i = i_ref[0].astype(jnp.float32)
    a_param = a_ref[...].astype(jnp.float32)  # (1, Bw)

    log_a = -c * jax.nn.softplus(a_param) * jax.nn.sigmoid(r)  # (Bs, Bw)
    a = jnp.exp(log_a)
    multiplier = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    inp = multiplier * jax.nn.sigmoid(i) * x

    acum, bcum = _linear_scan(a, inp)
    out = acum * h_ref[...] + bcum          # h_ref broadcasts (1, Bw)
    o_ref[0] = out.astype(o_ref.dtype)
    h_ref[...] = out[-1:]

    @pl.when(si == num_seq_chunks - 1)
    def _final():
        hlast_ref[0] = out[-1:].astype(hlast_ref.dtype)


def rglru_bsw(
    x: jax.Array,        # (B, S, W)
    r: jax.Array,        # (B, S, W)
    i: jax.Array,        # (B, S, W)
    a_param: jax.Array,  # (W,)
    h0: jax.Array,       # (B, W)
    *,
    c: float = 8.0,
    block_s: int = 256,
    block_w: int = 512,
    interpret: bool | None = None,
):
    b, s, w = x.shape
    block_s = min(block_s, s)
    block_w = min(block_w, w)
    assert s % block_s == 0 and w % block_w == 0, (s, w, block_s, block_w)
    ns, nw = s // block_s, w // block_w
    a2d = a_param.reshape(1, w)
    # the state travels as (B, 1, W): a (1, block_w) block of it is then
    # a full-extent row, which the TPU's (8, 128) tiling rule accepts
    h03d = h0.reshape(b, 1, w)

    kernel = functools.partial(
        _rglru_kernel, c=c, block_s=block_s, num_seq_chunks=ns
    )
    out, hlast = launch.pallas_call(
        kernel,
        name="rglru",
        grid=(b, nw, ns),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, block_w), lambda bi, wi, si: (0, wi)),
            pl.BlockSpec((1, 1, block_w), lambda bi, wi, si: (bi, 0, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, 1, block_w), lambda bi, wi, si: (bi, 0, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, w), x.dtype),
            jax.ShapeDtypeStruct((b, 1, w), x.dtype),
        ],
        scratch_shapes=[launch.VMEM((1, block_w), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        rows=b * s,
    )(x, r, i, a2d, h03d)
    return out, hlast.reshape(b, w)
