"""Public kernel entry points.

``impl`` selects the compute path:
  * "pallas"  — the Pallas kernel; compiled on TPU, interpret=True elsewhere
                (the CPU interpreter executes the kernel body in Python —
                this is how kernels are validated in this container).
  * "xla"     — the pure-jnp oracle from ref.py. This is the dry-run path so
                XLA ``cost_analysis()`` sees the FLOPs (pallas_call is opaque
                to it); on real TPU "pallas" is the production path.
  * "auto"    — "pallas" on TPU, "xla" otherwise.

All wrappers take the model-natural layouts and handle the kernel-layout
transposes / flattening internally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_bkgd
from repro.kernels.expert_ffn import expert_ffn_sorted
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.hsv_color import hsv_color_hist
from repro.kernels.launch import resolve_impl as _resolve
from repro.kernels.moe_router import moe_router_tk
from repro.kernels.rglru import rglru_bsw
from repro.kernels.ssd import ssd_bhcp


# --------------------------------------------------------------------------- #
def flash_attention(
    q: jax.Array,   # (B, S, H, D)
    k: jax.Array,   # (B, S, Hkv, D)
    v: jax.Array,   # (B, S, Hkv, Dv)
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,   # None: D ** -0.5
    impl: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
    chunk_q: int = ref.Q_CHUNK,
    unroll: bool = False,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "xla":
        return ref.mha_attention(q, k, v, causal=causal, window=window,
                                 scale=scale, chunk_q=chunk_q, unroll=unroll)

    b, s, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    group = h // hkv
    # pad seq to a block multiple; causal masking makes tail padding inert for
    # the valid rows (padded q rows are sliced off).
    blk = max(block_q, block_k)
    pad = (-s) % blk
    if pad:
        assert causal, "non-causal flash path requires block-aligned seq"
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    sp = s + pad
    qf = qp.transpose(0, 2, 1, 3).reshape(b * h, sp, d)
    kf = kp.transpose(0, 2, 1, 3).reshape(b * hkv, sp, d)
    vf = vp.transpose(0, 2, 1, 3).reshape(b * hkv, sp, dv)
    of = flash_attention_bhsd(
        qf, kf, vf,
        group=group, causal=causal, window=window, scale=scale,
        block_q=min(block_q, sp), block_k=min(block_k, sp),
    )
    out = of.reshape(b, h, sp, dv).transpose(0, 2, 1, 3)
    return out[:, :s]


def decode_attention(
    q: jax.Array,        # (B, H, D)
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,  # (B, S, Hkv, D)
    lengths: jax.Array,  # (B,)
    *,
    impl: str = "auto",
    block_k: int = 256,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "xla":
        return ref.decode_attention(q, k_cache, v_cache, lengths)

    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qf = q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    of = decode_attention_bkgd(
        qf, kf, vf, lengths,
        num_kv_heads=hkv, block_k=min(block_k, s),
    )
    return of.reshape(b, hkv, g, d).reshape(b, h, d)


def rglru(
    x: jax.Array,        # (B, S, W)
    r: jax.Array,
    i: jax.Array,
    a_param: jax.Array,  # (W,)
    h0: jax.Array | None = None,
    *,
    c: float = 8.0,
    impl: str = "auto",
    block_s: int = 256,
    block_w: int = 512,
):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.rglru(x, r, i, a_param, h0, c=c)
    b, s, w = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, w), x.dtype)
    return rglru_bsw(
        x, r, i, a_param, h0,
        c=c, block_s=min(block_s, s), block_w=min(block_w, w),
    )


def ssd(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)
    A: jax.Array,    # (H,)
    Bm: jax.Array,   # (B, S, G, N)
    Cm: jax.Array,   # (B, S, G, N)
    h0: jax.Array | None = None,
    *,
    chunk: int = 64,
    impl: str = "auto",
):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.ssd(x, dt, A, Bm, Cm, h0, chunk=chunk)
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)
    y, hl = ssd_bhcp(
        x.transpose(0, 2, 1, 3),
        dt.transpose(0, 2, 1),
        A,
        Bm.transpose(0, 2, 1, 3),
        Cm.transpose(0, 2, 1, 3),
        h0,
        chunk=min(chunk, s),
    )
    return y.transpose(0, 2, 1, 3), hl


def ssd_decode_step(x, dt, A, Bm, Cm, h, *, impl: str = "auto"):
    # O(1) recurrent step: pure-jnp path is already optimal (tiny tensors).
    return ref.ssd_decode_step(x, dt, A, Bm, Cm, h)


def hsv_color_classify(
    crops: jax.Array,              # (B, H, W, 3) RGB [0,255]
    ranges: jax.Array | None = None,
    *,
    impl: str = "auto",
    block_rows: int = 64,
):
    impl = _resolve(impl)
    if ranges is None:
        ranges = jnp.asarray(ref.COLOR_RANGES)
    if impl == "xla":
        return ref.hsv_color_classify(crops, ranges)
    hist = hsv_color_hist(
        crops, ranges,
        block_rows=min(block_rows, crops.shape[1]),
    )
    return hist, jnp.argmax(hist, axis=-1)


def moe_topk_router(logits: jax.Array, k: int, *, renormalize: bool = True,
                    impl: str = "auto", block_t: int = 1024):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.moe_topk_router(logits, k, renormalize=renormalize)
    t = logits.shape[0]
    return moe_router_tk(
        logits, k, renormalize=renormalize, block_t=min(block_t, t)
    )


def expert_ffn(
    x: jax.Array,        # (R, D) rows grouped by expert on tile boundaries
    w_gate: jax.Array,   # (E, D, F)
    w_up: jax.Array,     # (E, D, F)
    w_down: jax.Array,   # (E, F, D)
    offsets: jax.Array,  # (E + 1,) int32 from expert_ffn.padded_offsets
    *,
    block_rows: int = 128,
    impl: str = "auto",
) -> jax.Array:
    """Grouped SwiGLU of each group's rows by its expert's weights. Rows of
    ``x`` past ``offsets[E]`` come back unspecified on the Pallas path."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.expert_ffn(x, w_gate, w_up, w_down, offsets)
    return expert_ffn_sorted(x, w_gate, w_up, w_down, offsets,
                             block_rows=block_rows)
