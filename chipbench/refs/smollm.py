"""SmolLM (llama architecture) review scorer in plain ``jax.numpy``.

The published model (hf:HuggingFaceTB/SmolLM-135M, a ``LlamaForCausalLM``):
token embedding; per layer RMSNorm, grouped-query attention with rotary
embeddings (``rope_theta``, rotate-half), causal softmax at scale
``head_dim ** -0.5``, output projection, RMSNorm, SwiGLU MLP, each added to
the residual stream; final RMSNorm; logits through the tied embedding.
Norm weights are stored as offsets from one (a stored 0 is a gain of 1),
which is the layout the weights below are made in.

The review score is the program's UDF: for a row of real tokens
``x_1..x_n``, ``sum_t mean_{v in food} log p_t(v) - mean_{v in service}
log p_t(v)``. The log-normaliser of each position is the same for both
means and cancels, so the reference takes logits of the 100 food and
service words only; that is exact, and it keeps a block of rows small.
Positions past a row's last real token never reach it under the causal
mask, so each row is run at its real length (right-padded to a block).

``dot`` is the one place precision enters: the reference rounds nothing
and runs every product at ``HIGHEST``; the control (``fp8_dot``) rounds
both operands of every product to float8 e4m3 first.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

FOOD_WORDS = np.arange(10, 60)
SERVICE_WORDS = np.arange(60, 110)


def make_weights(sizes: Dict, seed: int, dtype=jnp.bfloat16):
    """Random weights in the program's parameter layout, made on the device
    in one jitted call: every matrix N(0, 0.02) rounded to ``dtype``, every
    norm offset 0. ``seed`` is below 2**31."""
    d, h, kv, f = (sizes["hidden_size"], sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["intermediate_size"])
    hd, n, v = d // h, sizes["num_hidden_layers"], sizes["vocab_size"]
    shapes = {
        "wq": (n, d, h, hd), "wk": (n, d, kv, hd), "wv": (n, d, kv, hd),
        "wo": (n, h, hd, d), "w_gate": (n, d, f), "w_up": (n, d, f),
        "w_down": (n, f, d),
    }

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes) + 1)
        layers = {name: (0.02 * jax.random.normal(k, s, jnp.float32)).astype(dtype)
                  for k, (name, s) in zip(keys[1:], sorted(shapes.items()))}
        layers["attn_norm"] = jnp.zeros((n, d), dtype)
        layers["mlp_norm"] = jnp.zeros((n, d), dtype)
        embed = (0.02 * jax.random.normal(keys[0], (v, d), jnp.float32)).astype(dtype)
        return {"embed": embed, "final_norm": jnp.zeros((d,), dtype),
                "layers": layers}

    return make(jax.random.key(seed))


def f32_dot(eq: str, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def fp8_dot(eq: str, a, b):
    r = lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(eq, r(a), r(b), precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    s, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "dot"))
def _scores(weights, tokens, sizes, dot: Callable):
    """tokens (rows, S) int32, right-padded with 0 -> scores (rows,)."""
    sizes = dict(sizes)
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    w = jax.tree.map(lambda p: p.astype(jnp.float32), weights)
    x = w["embed"][tokens]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        a = _rms(x, lw["attn_norm"], eps)
        q = _rope(dot("bsd,dhk->bshk", a, lw["wq"]), theta)
        k = _rope(dot("bsd,dhk->bshk", a, lw["wk"]), theta)
        v = dot("bsd,dhk->bshk", a, lw["wv"])
        k, v = jnp.repeat(k, h // kv, 2), jnp.repeat(v, h // kv, 2)
        logits = dot("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        x = x + dot("bshk,hkd->bsd", dot("bhqk,bkhd->bqhd", p, v), lw["wo"])
        m = _rms(x, lw["mlp_norm"], eps)
        g = dot("bsd,df->bsf", m, lw["w_gate"])
        u = dot("bsd,df->bsf", m, lw["w_up"])
        return x + dot("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"]), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x, w["final_norm"], eps)
    food = dot("bsd,vd->bsv", x, w["embed"][FOOD_WORDS]).mean(-1)
    service = dot("bsd,vd->bsv", x, w["embed"][SERVICE_WORDS]).mean(-1)
    return ((food - service) * (tokens > 0)).sum(-1)


def scores(weights, rows, sizes: Dict, dot: Callable = f32_dot,
           block_rows: int = 32) -> np.ndarray:
    """Scores of ``rows`` (a list of 1-D int token arrays at their real
    length), in blocks of rows of like length."""
    key = tuple(sorted(sizes.items()))
    lens = np.array([len(r) for r in rows])
    out = np.zeros(len(rows), np.float64)
    order = np.argsort(lens, kind="stable")
    for i in range(0, len(rows), block_rows):
        idx = order[i:i + block_rows]
        s = 1 << int(np.ceil(np.log2(max(lens[idx].max(), 8))))
        toks = np.zeros((block_rows, s), np.int32)
        for j, r in enumerate(idx):
            toks[j, :lens[r]] = rows[r]
        out[idx] = np.asarray(_scores(weights, jnp.asarray(toks), key, dot))[:len(idx)]
    return out
