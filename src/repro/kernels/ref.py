"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references the kernel tests assert against
(``tests/test_kernels.py`` sweeps shapes/dtypes with assert_allclose), and
they double as the XLA compute path used by the dry-run so that
``cost_analysis()`` sees the FLOPs (Pallas calls are opaque to it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------- #
# attention                                                                    #
# --------------------------------------------------------------------------- #
def _gqa_expand(k: jax.Array, num_heads: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, H, D) by repeating kv heads per group."""
    b, s, hkv, d = k.shape
    group = num_heads // hkv
    return jnp.repeat(k, group, axis=2)


def _attn_dense(q, k, v, *, causal, window, q_offset, k_offset=0, kv_len=None,
                scale=None):
    """One dense attention tile; q (B,Sq,H,D) vs k (B,Sk,H,D), v (B,Sk,H,Dv),
    fp32 math."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits *= scale
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :] + k_offset
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    mask = mask[None, None]
    if kv_len is not None:
        # kv_len masks ABSOLUTE positions (kpos includes k_offset)
        mask = mask & (kpos[None, None] < kv_len[:, None, None, None])
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


Q_CHUNK = 512  # XLA-path q blocking: bounds the live S x S score tile


def mha_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    window: int = 0,            # >0: sliding window (causal)
    scale: float | None = None,  # softmax scale; None: D ** -0.5
    q_offset: int = 0,          # absolute position of q[0] (for decode/chunks)
    kv_len: jax.Array | None = None,  # (B,) valid kv length (masks the rest)
    chunk_q: int = Q_CHUNK,     # 0 disables chunking (dense)
    unroll: bool = False,       # Python-unroll chunks (exact FLOPs accounting)
) -> jax.Array:
    """Reference attention: GQA, causal, sliding-window, length masking.

    Memory-bounded XLA formulation: q is processed in chunk_q blocks under a
    checkpointed ``lax.map`` (so only one (Bq, Sk) score tile is live — the
    flash-kernel working-set property, expressed in XLA). Sliding-window
    attention slices k/v to a (window + chunk) band per block, keeping both
    memory AND compiled FLOPs sub-quadratic for SWA archs (h2o-danube).

    ``unroll=True`` emits the chunks as straight-line HLO instead of a map —
    used by the roofline delta-lowerings, because XLA ``cost_analysis()``
    counts a map body ONCE (calibrated; see roofline/analysis.py).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    k = _gqa_expand(k, h)
    v = _gqa_expand(v, h)

    if chunk_q <= 0 or sq <= chunk_q or sq % chunk_q != 0:
        return _attn_dense(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, kv_len=kv_len, scale=scale)

    nchunks = sq // chunk_q
    qc = q.reshape(b, nchunks, chunk_q, h, d)
    banded = window > 0 and window + chunk_q < sk
    band = window + chunk_q

    def one(qb, ci):
        if banded:
            start = jnp.clip(ci * chunk_q - window, 0, sk - band)
            kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            return _attn_dense(
                qb, kb, vb, causal=causal, window=window,
                q_offset=q_offset + ci * chunk_q, k_offset=start, kv_len=kv_len,
                scale=scale,
            )
        return _attn_dense(
            qb, k, v, causal=causal, window=window,
            q_offset=q_offset + ci * chunk_q, kv_len=kv_len, scale=scale,
        )

    if unroll:
        outs = [one(qc[:, i], jnp.int32(i)) for i in range(nchunks)]
        out = jnp.stack(outs, axis=0)
    else:
        fn = jax.checkpoint(lambda args: one(*args))
        out = jax.lax.map(fn, (jnp.moveaxis(qc, 1, 0), jnp.arange(nchunks)))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, v.shape[-1])


def decode_attention(
    q: jax.Array,       # (B, H, D) one new token per sequence
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,  # (B, S, Hkv, D)
    lengths: jax.Array,  # (B,) int32 — number of valid cache entries
) -> jax.Array:
    out = mha_attention(
        q[:, None], k_cache, v_cache, causal=False, kv_len=lengths
    )
    return out[:, 0]


# --------------------------------------------------------------------------- #
# RG-LRU (recurrentgemma / griffin)                                            #
# --------------------------------------------------------------------------- #
def rglru(
    x: jax.Array,        # (B, S, W) gated input
    r: jax.Array,        # (B, S, W) recurrence gate pre-activation
    i: jax.Array,        # (B, S, W) input gate pre-activation
    a_param: jax.Array,  # (W,) learnable Lambda pre-activation
    h0: jax.Array | None = None,  # (B, W) initial state
    *,
    c: float = 8.0,
):
    """RG-LRU: h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t).

    a_t = exp(-c * softplus(a_param) * sigmoid(r_t)). Returns (h_seq, h_last).
    """
    b, s, w = x.shape
    xf = x.astype(jnp.float32)
    log_a = -c * jax.nn.softplus(a_param.astype(jnp.float32)) * jax.nn.sigmoid(
        r.astype(jnp.float32)
    )
    a = jnp.exp(log_a)
    gated = jax.nn.sigmoid(i.astype(jnp.float32)) * xf
    # sqrt(1 - a^2) computed in log space for stability
    multiplier = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    inp = multiplier * gated
    h0 = jnp.zeros((b, w), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, t):
        h = a[:, t] * h + inp[:, t]
        return h, h

    h_last, hs = jax.lax.scan(step, h0, jnp.arange(s))
    return jnp.swapaxes(hs, 0, 1).astype(x.dtype), h_last.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Mamba-2 SSD (state-space duality)                                            #
# --------------------------------------------------------------------------- #
def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < k <= i} x[..., k]."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), dtype=bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd(
    x: jax.Array,    # (B, S, H, P) inputs (already multiplied by dt outside? no: raw)
    dt: jax.Array,   # (B, S, H) positive step sizes
    A: jax.Array,    # (H,) negative-log decay parameter (A < 0 effective)
    Bm: jax.Array,   # (B, S, G, N)
    Cm: jax.Array,   # (B, S, G, N)
    h0: jax.Array | None = None,  # (B, H, P, N)
    *,
    chunk: int = 64,
):
    """Chunked SSD (Mamba-2). G (B/C groups) must divide H. Returns (y, h_last).

    y_t = C_t^T sum_{s<=t} (prod_{s<r<=t} exp(A*dt_r)) dt_s B_s x_s
    computed chunkwise: quadratic intra-chunk + recurrent inter-chunk states.
    """
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    assert h % g == 0
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = h // g

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)  # (B,S,H,N)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)

    # reshape into chunks
    xc = xf.reshape(b, nc, chunk, h, p)
    dtc = dtf.reshape(b, nc, chunk, h)
    Bc = Bf.reshape(b, nc, chunk, h, n)
    Cc = Cf.reshape(b, nc, chunk, h, n)

    dA = dtc * Af[None, None, None, :]          # (B,NC,L,H) log-decay per step
    dA = jnp.moveaxis(dA, -1, 2)                # (B,NC,H,L)
    dA_cum = jnp.cumsum(dA, axis=-1)            # (B,NC,H,L)

    # ---- intra-chunk (quadratic) ----
    Lmat = jnp.exp(_segsum(dA))                 # (B,NC,H,L,L)
    scores = jnp.einsum("bchln,bcmhn->bchlm", jnp.moveaxis(Cc, 3, 2), Bc)
    # scores[b,c,h,l,m] = C_l . B_m ; weight by Lmat and dt_m
    att = scores * Lmat * jnp.moveaxis(dtc, -1, 2)[:, :, :, None, :]
    y_intra = jnp.einsum("bchlm,bcmhp->bclhp", att, xc)

    # ---- chunk states ----
    decay_to_end = jnp.exp(dA_cum[..., -1:] - dA_cum)  # (B,NC,H,L)
    states = jnp.einsum(
        "bclhn,bchl,bclh,bclhp->bchpn", Bc, decay_to_end, dtc, xc
    )  # (B,NC,H,P,N)

    # ---- inter-chunk recurrence ----
    chunk_decay = jnp.exp(dA_cum[..., -1])  # (B,NC,H) total decay of chunk

    def scan_fn(hprev, inputs):
        st, dec = inputs  # (B,H,P,N), (B,H)
        hnew = hprev * dec[..., None, None] + st
        return hnew, hprev  # emit state ENTERING this chunk

    h0f = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if h0 is None
        else h0.astype(jnp.float32)
    )
    h_last, h_in = jax.lax.scan(
        scan_fn,
        h0f,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_in = jnp.moveaxis(h_in, 0, 1)  # (B,NC,H,P,N) state at chunk start

    # ---- inter-chunk output ----
    in_decay = jnp.exp(dA_cum)  # decay from chunk start to position l
    y_inter = jnp.einsum(
        "bclhn,bchl,bchpn->bclhp", Cc, in_decay, h_in
    )

    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y.astype(x.dtype), h_last.astype(jnp.float32)


def ssd_decode_step(
    x: jax.Array,    # (B, H, P) one token
    dt: jax.Array,   # (B, H)
    A: jax.Array,    # (H,)
    Bm: jax.Array,   # (B, G, N)
    Cm: jax.Array,   # (B, G, N)
    h: jax.Array,    # (B, H, P, N) state
):
    """Single recurrent SSD step. Returns (y, h_new)."""
    b, hh, p = x.shape
    g = Bm.shape[1]
    rep = hh // g
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=1)  # (B,H,N)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=1)
    dA = jnp.exp(dt.astype(jnp.float32) * A.astype(jnp.float32))  # (B,H)
    hnew = h * dA[..., None, None] + jnp.einsum(
        "bhn,bh,bhp->bhpn", Bf, dt.astype(jnp.float32), x.astype(jnp.float32)
    )
    y = jnp.einsum("bhn,bhpn->bhp", Cf, hnew)
    return y.astype(x.dtype), hnew


# --------------------------------------------------------------------------- #
# HSV color classification (the paper's DogColorClassifier)                    #
# --------------------------------------------------------------------------- #
# ranges follow the paper's example: red = (0,50,70)..(9,255,255), etc.
COLOR_NAMES = (
    "red", "black", "gray", "yellow", "green", "blue", "purple", "pink",
    "white", "other",
)
# (lo_h, lo_s, lo_v, hi_h, hi_s, hi_v) with H in [0,180), S,V in [0,256)
COLOR_RANGES = np.array(
    [
        [0, 50, 70, 9, 255, 255],      # red
        [0, 0, 0, 180, 255, 45],       # black
        [0, 0, 46, 180, 50, 200],      # gray
        [20, 50, 70, 33, 255, 255],    # yellow
        [34, 50, 70, 85, 255, 255],    # green
        [86, 50, 70, 128, 255, 255],   # blue
        [129, 50, 70, 158, 255, 255],  # purple
        [159, 50, 70, 177, 255, 255],  # pink
        [0, 0, 201, 180, 49, 255],     # white
    ],
    dtype=np.float32,
)


def rgb_to_hsv(rgb: jax.Array) -> jax.Array:
    """RGB in [0,255] -> HSV with H in [0,180), S,V in [0,255] (OpenCV scale)."""
    r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
    mx = jnp.maximum(jnp.maximum(r, g), b)
    mn = jnp.minimum(jnp.minimum(r, g), b)
    diff = mx - mn
    safe = jnp.where(diff == 0, 1.0, diff)
    h = jnp.where(
        mx == r,
        (g - b) / safe % 6.0,
        jnp.where(mx == g, (b - r) / safe + 2.0, (r - g) / safe + 4.0),
    )
    h = jnp.where(diff == 0, 0.0, h) * 30.0  # 60/2 — OpenCV H/2 convention
    s = jnp.where(mx == 0, 0.0, diff / jnp.where(mx == 0, 1.0, mx)) * 255.0
    return jnp.stack([h, s, mx], axis=-1)


def hsv_color_classify(crops: jax.Array, ranges: jax.Array | None = None):
    """(B, H, W, 3) RGB [0,255] -> (B, n_colors+1) pixel-fraction histogram.

    Class = argmax fraction (last bucket = 'other'). Returns (hist, label).
    """
    if ranges is None:
        ranges = jnp.asarray(COLOR_RANGES)
    hsv = rgb_to_hsv(crops.astype(jnp.float32))  # (B,H,W,3)
    px = hsv[:, :, :, None, :]  # (B,H,W,1,3)
    lo = ranges[None, None, None, :, 0:3]
    hi = ranges[None, None, None, :, 3:6]
    inrange = jnp.all((px >= lo) & (px <= hi), axis=-1)  # (B,H,W,C)
    # first matching bucket wins (paper checks ranges in order)
    first = jnp.cumsum(inrange, axis=-1) == 1
    inrange = inrange & first
    other = ~jnp.any(inrange, axis=-1, keepdims=True)
    onehot = jnp.concatenate([inrange, other], axis=-1).astype(jnp.float32)
    hist = onehot.mean(axis=(1, 2))  # (B, C+1)
    return hist, jnp.argmax(hist, axis=-1)


# --------------------------------------------------------------------------- #
# MoE top-k router                                                             #
# --------------------------------------------------------------------------- #
def moe_topk_router(logits: jax.Array, k: int, *, renormalize: bool = True):
    """(T, E) -> (weights (T,k), idx (T,k) int32): the k largest softmax
    probabilities, renormalized to sum to 1 when ``renormalize``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, k)
    weights = vals / jnp.sum(vals, axis=-1, keepdims=True) if renormalize else vals
    return weights.astype(logits.dtype), idx.astype(jnp.int32)


# --------------------------------------------------------------------------- #
# grouped expert FFN (kernels/expert_ffn.py)                                   #
# --------------------------------------------------------------------------- #
def expert_ffn(x, w_gate, w_up, w_down, offsets):
    """Rows grouped by expert at ``offsets`` (E + 1,), as the kernel takes
    them: row r of group e -> down_e(silu(x_r gate_e) * (x_r up_e)), with
    float32 accumulation; rows outside every group -> 0. Dense over the
    experts (each row is computed by every expert and masked)."""
    rows = jnp.arange(x.shape[0])
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        g = jnp.dot(x, w_gate[e], preferred_element_type=jnp.float32)
        u = jnp.dot(x, w_up[e], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = jnp.dot(h, w_down[e], preferred_element_type=jnp.float32)
        mine = (rows >= offsets[e]) & (rows < offsets[e + 1])
        out = jnp.where(mine[:, None], y, out)
    return out.astype(x.dtype)
