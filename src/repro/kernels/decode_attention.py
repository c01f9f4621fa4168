"""GQA flash-decode (Pallas TPU): one new token vs a long KV cache.

Layout: q reshaped to (B, Hkv, G, D) — the G query heads of one kv group are
processed together so the (G, D) x (D, Bk) contraction feeds the MXU.
Grid (B*Hkv, num_kv_blocks), kv innermost with online-softmax scratch.
Valid-length masking comes from a per-sequence ``lengths`` array so the same
executable serves any fill level of the cache (no recompilation per step —
this is the TPU analogue of Hydro's batch-agnostic workers). ``lengths`` is
a scalar-prefetch operand: it sits in SMEM before the grid runs, and the
body reads its row's entry as a scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch

NEG_INF = -1e30


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, block_k: int, num_kv_blocks: int, num_kv_heads: int,
):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0) // num_kv_heads]
    k_start = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)   # (G, D)
        k = k_ref[0].astype(jnp.float32)   # (Bk, D)
        v = v_ref[0].astype(jnp.float32)   # (Bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                           # (G, Bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    # Skip cache blocks entirely beyond the valid length.
    pl.when(k_start < length)(_compute)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_bkgd(
    q: jax.Array,        # (B*Hkv, G, D)
    k_cache: jax.Array,  # (B*Hkv, S, D)
    v_cache: jax.Array,  # (B*Hkv, S, D)
    lengths: jax.Array,  # (B,) int32
    *,
    num_kv_heads: int,
    scale: float | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    bh, g, d = q.shape
    s = k_cache.shape[1]
    block_k = min(block_k, s)
    assert s % block_k == 0, (s, block_k)
    nk = s // block_k
    scale = (d ** -0.5) if scale is None else scale
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, num_kv_blocks=nk,
        num_kv_heads=num_kv_heads,
    )
    return launch.pallas_call(
        kernel,
        name="decode_attention",
        grid=(bh, nk),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda b, ki, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, lens: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, lens: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda b, ki, lens: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, d), q.dtype),
        scratch_shapes=[
            launch.VMEM((g, d), jnp.float32),
            launch.VMEM((g, 1), jnp.float32),
            launch.VMEM((g, 1), jnp.float32),
        ],
        dimension_semantics=("parallel", "arbitrary"),
        interpret=interpret,
        rows=bh * g,
        num_scalar_prefetch=1,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)
