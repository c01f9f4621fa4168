"""DeepSeek-V2 review scorer in plain ``jax.numpy``.

The published model (hf:deepseek-ai/DeepSeek-V2-Lite, a
``DeepseekV2ForCausalLM``): token embedding; per layer RMSNorm, multi-head
latent attention, RMSNorm, then an FFN, each added to the residual stream;
final RMSNorm; logits through the untied head. Norm weights are stored as
offsets from one (a stored 0 is a gain of 1), the layout the weights below
are made in.

Attention (no q compression): ``q = x Wq`` split into a no-rotary part and
a rotary part; ``[c, k_pe] = x Wkv_a``; ``c`` through its own RMSNorm;
``[k_nope, v] = c Wkv_b``; the rotary parts rotated with YaRN's
frequencies, the key's rotary part one head shared by every head; causal
softmax at ``(nope + rope)^-0.5 * m^2``, ``m = 0.1 mscale_all_dim ln(factor)
+ 1``; output projection. The rotary layout is HF's: the rope dims are
de-interleaved (even dims, then odd) before rotate-half.

FFN: the first ``first_k_dense_replace`` layers a SwiGLU of width
``intermediate_size``; the others the routed experts, computed densely over
every expert and weighted by the gate (the softmax probability where the
expert is among the token's greedy top ``num_experts_per_tok``, 0 where
not; renormalised when ``norm_topk_prob``; times ``routed_scaling_factor``),
plus the shared experts as one SwiGLU of width ``moe_intermediate_size *
n_shared_experts``. The router's logits are taken from the float32 stream.

The review score, as in ``refs/smollm.py``: for a row of real tokens, the
sum over positions of the mean log-probability of the 50 food words minus
that of the 50 service words; the log-normaliser cancels, so only those 100
logits are taken. Each row runs at its real length (right-padded to a
block), since under the causal mask no position reads a later one.

The weights stay in the bfloat16 they were made in and each layer, and each
expert, is upcast to float32 inside the loop over them: a float32 copy of
all the weights would not fit on one chip beside the program's.

``dot`` is the one place precision enters: the reference rounds nothing
and runs every product at ``HIGHEST``; the control (``fp8_dot``) rounds
both operands of every product to float8 e4m3 first.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

FOOD_WORDS = np.arange(10, 60)
SERVICE_WORDS = np.arange(60, 110)


def _shapes(s: Dict) -> Dict:
    d, h, r = s["hidden_size"], s["num_attention_heads"], s["kv_lora_rank"]
    nope, rope, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    e, fe, fd = s["n_routed_experts"], s["moe_intermediate_size"], s["intermediate_size"]
    fs = fe * s["n_shared_experts"]
    v = s["vocab_size"]
    nd = s["first_k_dense_replace"]
    nm = s["num_hidden_layers"] - nd

    def attn(n):
        return {"attn_norm": (n, d), "wq": (n, d, h, nope + rope),
                "wkv_a": (n, d, r + rope), "kv_norm": (n, r),
                "wkv_b": (n, r, h, nope + dv), "wo": (n, h, dv, d),
                "mlp_norm": (n, d)}

    return {
        "embed": (v, d), "final_norm": (d,), "out_head": (d, v),
        "dense_layers": dict(attn(nd), w_gate=(nd, d, fd), w_up=(nd, d, fd),
                             w_down=(nd, fd, d)),
        "moe_layers": dict(attn(nm), router=(nm, d, e), e_gate=(nm, e, d, fe),
                           e_up=(nm, e, d, fe), e_down=(nm, e, fe, d),
                           s_gate=(nm, d, fs), s_up=(nm, d, fs), s_down=(nm, fs, d)),
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, shape, dtype):
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_weights(sizes: Dict, seed: int, dtype=jnp.bfloat16):
    """Random weights in the program's parameter layout
    (``repro.models.deepseek.param_shapes``), made on the device one array
    at a time: every matrix N(0, 0.02) rounded to ``dtype``, every norm
    offset 0. ``seed`` is below 2**31. The vocabulary must be a multiple
    of 256, so that the program pads none."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(sizes), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(seed), len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        if jax.tree_util.keystr(path).endswith("norm']"):
            out.append(jnp.zeros(shape, dtype))
        else:
            out.append(_normal(k, shape, dtype))
    return jax.tree.unflatten(treedef, out)


def f32_dot(eq: str, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def fp8_dot(eq: str, a, b):
    r = lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(eq, r(a), r(b), precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn(s):
    """YaRN's inverse frequencies (rope / 2,), cos/sin factor and the
    softmax scale, from the published ``rope_scaling`` group."""
    dim, base = s["qk_rope_head_dim"], s["rope_theta"]
    y = dict(s["rope_scaling"])
    factor, orig = y["factor"], y["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq = inter * ramp + extra * (1 - ramp)
    cos_sin = _yarn_mscale(factor, y["mscale"]) / _yarn_mscale(factor, y["mscale_all_dim"])
    m = _yarn_mscale(factor, y["mscale_all_dim"])
    scale = (s["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return inv_freq.astype(np.float32), cos_sin, scale


def _rope(x, inv_freq, cos_sin):
    """x (b, s, h, rope): de-interleave, rotate-half at positions 0..s-1."""
    n = x.shape[1]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    ang = np.arange(n, dtype=np.float32)[:, None] * inv_freq
    cos = jnp.asarray(np.cos(ang) * cos_sin)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * cos_sin)[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, g, u, d, dot):
    return dot("bsf,fd->bsd", jax.nn.silu(dot("bsd,df->bsf", x, g))
               * dot("bsd,df->bsf", x, u), d)


@functools.partial(jax.jit, static_argnames=("sizes", "dot"))
def _scores(weights, tokens, sizes, dot: Callable):
    """tokens (rows, S) int32, right-padded with 0 -> scores (rows,)."""
    s = {k: (dict(v) if isinstance(v, tuple) else v) for k, v in sizes}
    h, r, eps = s["num_attention_heads"], s["kv_lora_rank"], s["rms_norm_eps"]
    nope, rope = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
    k_top, e_num = s["num_experts_per_tok"], s["n_routed_experts"]
    inv_freq, cos_sin, scale = _yarn(s)
    up = lambda t: jax.tree.map(lambda p: p.astype(jnp.float32), t)
    x = weights["embed"][tokens].astype(jnp.float32)
    n = tokens.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))

    def attention(x, lw):
        a = _rms(x, lw["attn_norm"], eps)
        q = dot("bsd,dhk->bshk", a, lw["wq"])
        ckv = dot("bsd,dk->bsk", a, lw["wkv_a"])
        c = _rms(ckv[..., :r], lw["kv_norm"], eps)
        kv = dot("bsr,rhk->bshk", c, lw["wkv_b"])
        q_pe = _rope(q[..., nope:], inv_freq, cos_sin)
        k_pe = _rope(ckv[:, :, None, r:], inv_freq, cos_sin)
        q = jnp.concatenate([q[..., :nope], q_pe], -1)
        k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_pe, h, 2)], -1)
        logits = dot("bqhd,bkhd->bhqk", q, k) * scale
        p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        return x + dot("bshk,hkd->bsd", dot("bhqk,bkhd->bqhd", p, kv[..., nope:]),
                       lw["wo"])

    def dense_layer(x, lw):
        lw = up(lw)
        x = attention(x, lw)
        m = _rms(x, lw["mlp_norm"], eps)
        return x + _swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], dot), None

    def moe_layer(x, lw):
        experts = ("e_gate", "e_up", "e_down")
        ew = [lw[k] for k in experts]
        lw = up({k: v for k, v in lw.items() if k not in experts})
        x = attention(x, lw)
        m = _rms(x, lw["mlp_norm"], eps)
        probs = jax.nn.softmax(dot("bsd,de->bse", m, lw["router"]), -1)
        vals, idx = jax.lax.top_k(probs, k_top)
        if s["norm_topk_prob"]:
            vals = vals / vals.sum(-1, keepdims=True)
        gate = (jax.nn.one_hot(idx, e_num) * vals[..., None]).sum(-2)
        gate = gate * s["routed_scaling_factor"]                 # (b, s, E)

        def expert(y, xs):
            g, *w = xs
            return y + g[..., None] * _swiglu(m, *up(w), dot), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                            (jnp.moveaxis(gate, -1, 0), *ew))
        y = y + _swiglu(m, lw["s_gate"], lw["s_up"], lw["s_down"], dot)
        return x + y, None

    x, _ = jax.lax.scan(dense_layer, x, weights["dense_layers"])
    x, _ = jax.lax.scan(moe_layer, x, weights["moe_layers"])
    x = _rms(x, weights["final_norm"].astype(jnp.float32), eps)
    head = weights["out_head"].astype(jnp.float32)
    food = dot("bsd,dv->bsv", x, head[:, FOOD_WORDS]).mean(-1)
    service = dot("bsd,dv->bsv", x, head[:, SERVICE_WORDS]).mean(-1)
    return ((food - service) * (tokens > 0)).sum(-1)


def _static(sizes: Dict):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
                        for k, v in sizes.items()))


def scores(weights, rows, sizes: Dict, dot: Callable = f32_dot,
           block_rows: int = 32, min_len: int = 64) -> np.ndarray:
    """Scores of ``rows`` (a list of 1-D int token arrays at their real
    length), in blocks of rows of like length, each padded to a power of
    two of at least ``min_len`` positions (few block shapes, few compiles)."""
    key = _static(sizes)
    lens = np.array([len(r) for r in rows])
    out = np.zeros(len(rows), np.float64)
    order = np.argsort(lens, kind="stable")
    for i in range(0, len(rows), block_rows):
        idx = order[i:i + block_rows]
        s = 1 << int(np.ceil(np.log2(max(lens[idx].max(), min_len))))
        toks = np.zeros((block_rows, s), np.int32)
        for j, r in enumerate(idx):
            toks[j, :lens[r]] = rows[r]
        out[idx] = np.asarray(_scores(weights, jnp.asarray(toks), key, dot))[:len(idx)]
    return out
