"""Multi-head latent attention (DeepSeek-V2), without q compression.

Per layer, for x (B, S, D):

  q = x Wq -> (H, nope + rope), split [q_nope, q_pe]
  [c, k_pe] = x Wkv_a, c of width kv_lora_rank, k_pe of width rope
  c = RMSNorm(c)
  [k_nope, v] = c Wkv_b -> (H, nope + v_head_dim)
  q_pe, k_pe rotated by YaRN rotary; k_pe is one head shared by all heads
  q = [q_nope, q_pe], k = [k_nope, k_pe]
  o = causal softmax(q k^T * scale) v, scale = (nope + rope)^-0.5 * m^2
  out = o Wo

The rotary follows HF ``modeling_deepseek.py``: the rope dims are
de-interleaved (even dims first, then odd) before rotate-half, and the
frequencies are YaRN's blend of the base and the interpolated ones. Queries
and keys are 192 wide and values 128 at DeepSeek-V2-Lite's widths, so the
flash kernel runs with a value width of its own. The latent ``c`` is not
kept: this is the scorer's forward pass, with no cache.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import rms_norm

SDS = jax.ShapeDtypeStruct


def param_shapes(cfg, layers: int, dtype):
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "attn_norm": SDS((layers, d), dtype),
        "wq": SDS((layers, d, h, nope + rope), dtype),
        "wkv_a": SDS((layers, d, r + rope), dtype),
        "kv_norm": SDS((layers, r), dtype),
        "wkv_b": SDS((layers, r, h, nope + dv), dtype),
        "wo": SDS((layers, h, dv, d), dtype),
    }


def _correction_dim(rotations: float, dim: int, base: float, max_pos: int) -> float:
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) / (2 * math.log(base))


def rope_inv_freq(cfg) -> jax.Array:
    """(rope / 2,) inverse frequencies; YaRN's blend when ``rope_scaling``."""
    dim, base, ys = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if ys is None:
        return extra
    inter = extra / ys.factor
    low = max(math.floor(_correction_dim(ys.beta_fast, dim, base,
                                         ys.original_max_position_embeddings)), 0)
    high = min(math.ceil(_correction_dim(ys.beta_slow, dim, base,
                                         ys.original_max_position_embeddings)), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp          # 1: the base frequency, 0: the interpolated one
    return inter * (1 - keep) + extra * keep


def rope_mscale(cfg) -> float:
    """The factor on cos and sin (1.0 when mscale == mscale_all_dim)."""
    ys = cfg.rope_scaling
    if ys is None:
        return 1.0
    return ys.get_mscale(ys.factor, ys.mscale) / ys.get_mscale(ys.factor, ys.mscale_all_dim)


def softmax_scale(cfg) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        m = ys.get_mscale(ys.factor, ys.mscale_all_dim)
        scale *= m * m
    return scale


def rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
         mscale: float) -> jax.Array:
    """x (B, S, H, R), positions (B, S): de-interleave, then rotate-half."""
    b, s, h, r = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, h, r // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]              # even dims, odd dims
    ang = positions[..., None].astype(jnp.float32) * inv_freq   # (B, S, R/2)
    cos = (jnp.cos(ang) * mscale)[:, :, None, :]
    sin = (jnp.sin(ang) * mscale)[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def attention(cfg, lp, x, positions):
    """(B, S, D) -> (B, S, D), causal, attention in ``cfg.attention_impl``."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope_dim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(dt))
    ckv = jnp.einsum("bsd,dk->bsk", x, lp["wkv_a"].astype(dt))
    c = rms_norm(ckv[..., :r], lp["kv_norm"], cfg.norm_eps)
    kv = jnp.einsum("bsr,rhk->bshk", c, lp["wkv_b"].astype(dt))
    inv_freq, mscale = rope_inv_freq(cfg), rope_mscale(cfg)
    q_pe = rope(q[..., nope:], positions, inv_freq, mscale)
    k_pe = rope(ckv[..., None, r:], positions, inv_freq, mscale)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope_dim))],
                        axis=-1)
    o = ops.flash_attention(
        q, k, kv[..., nope:], causal=True, scale=softmax_scale(cfg),
        impl=cfg.attention_impl, chunk_q=cfg.attention_chunk_q,
        unroll=cfg.attention_unroll,
    )
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(dt))
