"""Blocked causal flash attention (Pallas TPU), with GQA + sliding window.

Layout: q/k flattened to (B*H, S, D) / (B*Hkv, S, D), v to (B*Hkv, S, Dv)
(latent attention's values are narrower than its queries and keys); grid
(BH, num_q_blocks, num_kv_blocks) with the kv dimension innermost
("arbitrary" semantics) carrying the online-softmax state (m, l, acc) in VMEM
scratch. Causal / sliding-window blocks that are fully masked are skipped
with ``pl.when`` so the kernel does ~half (causal) or O(window) work.

VMEM working set per program: q and k blocks (Bq, D), (Bk, D), a v block
(Bk, Dv), acc (Bq, Dv) f32 + stats — with Bq=Bk=128, D, Dv <= 256 this is
< 0.5 MB, far under the ~16 MB v5e VMEM budget; MXU contractions are
(128, D)x(D, 128) and (128, 128)x(128, Dv).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: int, block_q: int, block_k: int,
    num_kv_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (Bq, D)
        k = k_ref[0].astype(jnp.float32)          # (Bk, D)
        v = v_ref[0].astype(jnp.float32)          # (Bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                  # (Bq, Bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=bool)
        if causal:
            mask = mask & (kpos <= qpos)
        if window > 0:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                        # (Bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (Bq, Bk)
        correction = jnp.exp(m_prev - m_new)       # (Bq, 1)
        l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * correction + pv
        m_ref[...] = m_new

    if causal or window > 0:
        # Block-level visibility: skip fully-masked blocks entirely, so the
        # kernel does ~half (causal) or O(window/seq) (SWA) of the work.
        visible = jnp.asarray(True)
        if causal:
            visible = visible & (k_start <= q_start + block_q - 1)
        if window > 0:
            visible = visible & (k_start + block_k - 1 >= q_start - window + 1)
        pl.when(visible)(_compute)
    else:
        _compute()

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows -> 0 output
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,   # (BH, Sq, D)
    k: jax.Array,   # (BHkv, Sk, D)
    v: jax.Array,   # (BHkv, Sk, Dv)
    *,
    group: int,     # H // Hkv
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k
    scale = (d ** -0.5) if scale is None else scale

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk,
    )
    grid = (bh, nq, nk)
    return launch.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki, g=group: (b // g, ki, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, qi, ki, g=group: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            launch.VMEM((block_q, dv), jnp.float32),
            launch.VMEM((block_q, 1), jnp.float32),
            launch.VMEM((block_q, 1), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        rows=bh * sq,
    )(q, k, v)
