"""Fused MoE top-k gating (Pallas TPU).

softmax over experts + iterative greedy top-k (k is small and static: 2 for
arctic and grok, 6 for DeepSeek-V2-Lite) + optional renormalization of the
k weights (on for arctic and grok; off for DeepSeek-V2, whose
``norm_topk_prob`` is false), in one VMEM-resident pass over the token
block. Grid (num_token_blocks,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch

NEG_INF = -1e30


def _router_kernel(logits_ref, w_ref, idx_ref, *, k: int, renormalize: bool):
    logits = logits_ref[...].astype(jnp.float32)          # (Bt, E)
    probs = jax.nn.softmax(logits, axis=-1)

    remaining = probs
    ws, idxs = [], []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)              # (Bt,)
        w = jnp.max(remaining, axis=-1)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, remaining.shape, 1)
            == idx[:, None]
        )
        remaining = jnp.where(onehot, NEG_INF, remaining)
        ws.append(w)
        idxs.append(idx)

    w = jnp.stack(ws, axis=-1)                            # (Bt, k)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w_ref[...] = w.astype(w_ref.dtype)
    idx_ref[...] = jnp.stack(idxs, axis=-1).astype(jnp.int32)


def moe_router_tk(
    logits: jax.Array,  # (T, E)
    k: int,
    *,
    renormalize: bool = True,
    block_t: int = 1024,
    interpret: bool | None = None,
):
    t, e = logits.shape
    block_t = min(block_t, t)
    assert t % block_t == 0, (t, block_t)
    nt = t // block_t

    kernel = functools.partial(_router_kernel, k=k, renormalize=renormalize)
    w, idx = launch.pallas_call(
        kernel,
        name="moe_router",
        grid=(nt,),
        in_specs=[pl.BlockSpec((block_t, e), lambda ti: (ti, 0))],
        out_specs=[
            pl.BlockSpec((block_t, k), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, k), lambda ti: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, k), logits.dtype),
            jax.ShapeDtypeStruct((t, k), jnp.int32),
        ],
        dimension_semantics=("parallel",),
        interpret=interpret,
        rows=t,
    )(logits)
    return w, idx
