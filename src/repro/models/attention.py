"""GQA attention: training/prefill (flash), decode (sequence-sharded cache).

Decode design (the memory-optimal layout for 32k caches, see DESIGN.md §5):
the KV cache shards its SEQUENCE dim over the "model" mesh axis. A
``shard_map`` computes per-shard partial softmax stats (m, l, o) and combines
them with a psum rescale — mathematically exact flash-decode across shards.
The new token's K/V is written by the owning shard via a masked dynamic
update. This sidesteps the kv-head divisibility problem entirely (kv_heads in
{1,3,4,8,12} vs a 16-way axis) and keeps per-chip cache at
batch/data x seq/model.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops, ref
from repro.models.layers import ShardCtx, rope


def qkv_proj(cfg, x, wq, wk, wv, ctx: ShardCtx):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(dt))
    q = ctx.constrain(q, "batch seq heads .")
    k = ctx.constrain(k, "batch seq kv_heads .")
    v = ctx.constrain(v, "batch seq kv_heads .")
    return q, k, v


def out_proj(x, wo, ctx: ShardCtx):
    out = jnp.einsum("bshk,hkd->bsd", x, wo.astype(x.dtype))
    # pin the einsum OUTPUT to the weight's d-sharding first: without this
    # the partitioner may choose the replicated-weights strategy and
    # all-gather wo (205 MB/layer, measured) instead of the 1.8 MB output
    out = ctx.constrain(out, "batch seq d_sharded")
    return ctx.constrain(out, "batch seq d_model")


def attention_train(
    cfg, x, lp, positions, ctx: ShardCtx, *, window: int = 0, causal: bool = True
):
    """Full training/prefill attention. lp: layer params dict with wq/wk/wv/wo."""
    q, k, v = qkv_proj(cfg, x, lp["wq"], lp["wk"], lp["wv"], ctx)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    o = ops.flash_attention(
        q, k, v, causal=causal, window=window, impl=cfg.attention_impl,
        chunk_q=getattr(cfg, "attention_chunk_q", 512),
        unroll=getattr(cfg, "attention_unroll", False),
    )
    return out_proj(o, lp["wo"], ctx), (k, v)


def cross_attention(cfg, x, lp, k, v, ctx: ShardCtx):
    """Decoder cross-attention over precomputed encoder K/V (no mask)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, lp["xwq"].astype(dt))
    q = ctx.constrain(q, "batch seq heads .")
    o = ops.flash_attention(q, k, v, causal=False, impl="xla")
    return out_proj(o, lp["xwo"], ctx)


# --------------------------------------------------------------------------- #
# decode with sequence-sharded KV cache                                        #
# --------------------------------------------------------------------------- #
def _local_decode(
    q, k_cache, v_cache, new_k, new_v, lengths, *, seq_per_shard, axis,
):
    """Body run per model-shard: update local cache slice, partial attention.

    q: (B, H, D); caches: (B, S_loc, Hkv, D); new_k/v: (B, Hkv, D);
    lengths: (B,) tokens already in cache (new token goes at this index).
    """
    sl = seq_per_shard
    offset = (jax.lax.axis_index(axis) * sl) if axis else 0
    local_idx = lengths - offset  # (B,) position of the new token locally

    def upd(c, nk, li):
        # row-wise select + ONE dynamic_update_slice: with the cache buffer
        # donated, XLA updates in place — a whole-array where() would force
        # a full cache copy per layer (measured in §Perf iteration 3).
        inb = (li >= 0) & (li < sl)
        lic = jnp.clip(li, 0, sl - 1)
        cur = jax.lax.dynamic_slice(c, (lic, 0, 0), (1,) + c.shape[1:])
        row = jnp.where(inb, nk[None].astype(c.dtype), cur)
        return jax.lax.dynamic_update_slice(c, row, (lic, 0, 0))

    k_cache = jax.vmap(upd)(k_cache, new_k, local_idx)
    v_cache = jax.vmap(upd)(v_cache, new_v, local_idx)

    # valid entries in THIS shard after the write
    local_len = jnp.clip(lengths + 1 - offset, 0, sl)

    out = _partial_softmax_attend(q, k_cache, v_cache, local_len, axis)
    return out, k_cache, v_cache


def _partial_softmax_attend(q, k_cache, v_cache, local_len, axis):
    """Grouped-head partial attention WITHOUT materializing expanded KV.

    q (B,H,D), caches (B,S,Hkv,D): contract per kv-head group so the cache
    is read ONCE at its stored width (bf16/fp8 — no f32 copy in HBM);
    f32 happens in the MXU accumulator via preferred_element_type.
    """
    b, h, d = q.shape
    sl, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    kc = k_cache if k_cache.dtype == qg.dtype else k_cache.astype(qg.dtype)
    vc = v_cache if v_cache.dtype == qg.dtype else v_cache.astype(qg.dtype)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qg, kc, preferred_element_type=jnp.float32,
    ) * (d ** -0.5)                                      # (B, Hkv, G, S) f32
    kpos = jnp.arange(sl)[None, None, None, :]
    s = jnp.where(kpos < local_len[:, None, None, None], s, -1e30)
    m = jnp.max(s, axis=-1)                              # (B, Hkv, G)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(vc.dtype), vc,
                   preferred_element_type=jnp.float32)

    if axis:
        g_m = jax.lax.pmax(m, axis)
        scale = jnp.exp(m - g_m)
        l = jax.lax.psum(l * scale, axis)
        o = jax.lax.psum(o * scale[..., None], axis)
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).astype(q.dtype)
    return out.reshape(b, h, d)


def _batch_spec(mesh, batch: int):
    """Batch-dim shard_map spec: ('pod','data') when divisible, else the
    largest prefix that divides, else replicated (the long_500k batch=1
    case — the data axis idles, recorded honestly in the roofline)."""
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    kept = []
    denom = 1
    for a in ba:
        if batch % (denom * sizes[a]) == 0:
            kept.append(a)
            denom *= sizes[a]
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def decode_attention_seqsharded(
    cfg, q, k_cache, v_cache, new_k, new_v, lengths, ctx: ShardCtx
):
    """q (B,H,D), caches (B,S,Hkv,D) with S sharded over 'model'."""
    model_size = ctx.axis_size("model")
    if ctx.mesh is None or model_size <= 1:
        out, kc, vc = _local_decode(
            q, k_cache, v_cache, new_k, new_v, lengths,
            seq_per_shard=k_cache.shape[1], axis=None,
        )
        return out, kc, vc

    mesh = ctx.mesh
    s = k_cache.shape[1]
    assert s % model_size == 0, (s, model_size)
    bspec = _batch_spec(mesh, q.shape[0])
    qs = P(bspec, None, None)
    cs = P(bspec, "model", None, None)
    ks = P(bspec, None, None)
    ls = P(bspec)
    fn = partial(_local_decode, seq_per_shard=s // model_size, axis="model")
    out, kc, vc = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(qs, cs, cs, ks, ks, ls),
        out_specs=(qs, cs, cs),
        check_vma=False,
    )(q, k_cache, v_cache, new_k, new_v, lengths)
    return out, kc, vc


def decode_attention_block(cfg, x, lp, cache_k, cache_v, lengths, ctx: ShardCtx,
                           *, window: int = 0):
    """One decode step through an attention block. x: (B, 1, D).

    Returns (out (B,1,D), new_cache_k, new_cache_v). ``window>0`` means the
    cache is a ring buffer of that size (positions stored mod window).
    """
    q, k, v = qkv_proj(cfg, x, lp["wq"], lp["wk"], lp["wv"], ctx)
    pos = lengths[:, None]  # (B, 1) absolute position of the new token
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]

    if window == 0:
        out, kc, vc = decode_attention_seqsharded(
            cfg, q1, cache_k, cache_v, k1, v1, lengths, ctx
        )
    else:
        out, kc, vc = _ring_decode(
            cfg, q1, cache_k, cache_v, k1, v1, lengths, window, ctx
        )
    return out_proj(out[:, None], lp["wo"], ctx), kc, vc


def _ring_decode(cfg, q, cache_k, cache_v, new_k, new_v, lengths, window, ctx):
    """SWA/local decode: ring-buffer cache of size ``window``.

    All slots are valid once length >= window; before that only the first
    ``length+1`` slots are. Softmax is permutation-invariant so slot order
    doesn't matter (RoPE already applied at absolute positions).
    """
    slot = lengths % window
    valid = jnp.minimum(lengths + 1, window)

    model_size = ctx.axis_size("model")
    if ctx.mesh is None or model_size <= 1 or window % model_size != 0:
        def upd(c, n, i):
            return jax.lax.dynamic_update_slice(c, n[None].astype(c.dtype), (i, 0, 0))

        kc = jax.vmap(upd)(cache_k, new_k, slot)
        vc = jax.vmap(upd)(cache_v, new_v, slot)
        out = ref.decode_attention(q, kc, vc, valid)
        return out, kc, vc

    mesh = ctx.mesh
    bspec = _batch_spec(mesh, q.shape[0])
    qs = P(bspec, None, None)
    cs = P(bspec, "model", None, None)
    ks = P(bspec, None, None)
    ls = P(bspec)

    def body(q, kc, vc, nk, nv, slot, valid):
        sl = kc.shape[1]
        offset = jax.lax.axis_index("model") * sl
        li = slot - offset

        def upd(c, n, i):
            inb = (i >= 0) & (i < sl)
            ic = jnp.clip(i, 0, sl - 1)
            return jnp.where(inb, jax.lax.dynamic_update_slice(c, n[None].astype(c.dtype), (ic, 0, 0)), c)

        kc = jax.vmap(upd)(kc, nk, li)
        vc = jax.vmap(upd)(vc, nv, li)
        local_valid = jnp.clip(valid - offset, 0, sl)
        out = _partial_softmax_attend(q, kc, vc, local_valid, "model")
        return out, kc, vc

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qs, cs, cs, ks, ks, ls, ls),
        out_specs=(qs, cs, cs),
        check_vma=False,
    )(q, cache_k, cache_v, new_k, new_v, slot, valid)
