"""DeepSeek-V2 decoder family (DeepSeek-V2-Lite), forward pass only.

Every layer is RMSNorm, multi-head latent attention (``models/mla.py``)
and RMSNorm, then an FFN, each added to the residual stream. The first
``first_dense_layers`` layers take a dense SwiGLU of width ``d_ff``; the
rest an MoE FFN:

  router logits in float32 (x and W_g upcast, as published);
  softmax over the experts, greedy top-k, weights renormalised only when
  ``router_renormalize`` (DeepSeek-V2-Lite: no, and scaled by 1.0);
  y = sum_topk w_e SwiGLU_e(x) + SwiGLU_shared(x), the shared experts
  acting as one SwiGLU of width moe_d_ff * num_shared_experts.

Then the final RMSNorm and the untied head.

Dispatch is dropless: every routed assignment of a real token is computed,
with no capacity. ``batch["token_mask"]`` (the scorer passes ``tokens >
0``) says which positions are real; padded positions go to no routed
expert. Under right padding and the causal mask no real position reads a
padded one, so a real position's output does not depend on them. Padded
positions still pass through attention, the shared experts and the dense
layer. The real assignments are sorted by expert into groups that start
on tile boundaries and run through the grouped expert kernel
(``kernels/expert_ffn.py``), then gathered back and weighted per token.

This is what a scorer needs, on one device: no prefill, decode, cache or
training step, so the family is not in ``configs.ARCHS``.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import expert_ffn as kexp
from repro.kernels import ops
from repro.models import mla
from repro.models import transformer as tf
from repro.models.layers import (
    NULL_CTX, ShardCtx, dtype_of, lm_logits, rms_norm, swiglu_mlp, trunc_normal,
)

SDS = jax.ShapeDtypeStruct

# rows per tile of the expert kernel: at DeepSeek-V2-Lite's ~47 real
# assignments per expert per 16-row batch, one tile per expert, so each
# expert's weights are read once per layer
EXPERT_BLOCK_ROWS = 128


# --------------------------------------------------------------------------- #
# parameters                                                                   #
# --------------------------------------------------------------------------- #
def param_shapes(cfg) -> Dict:
    d, vp, dt = cfg.d_model, cfg.vocab_padded, dtype_of(cfg)
    dense, moe = cfg.first_dense_layers, cfg.num_layers - cfg.first_dense_layers
    e, fe = cfg.num_experts, cfg.moe_d_ff
    fs = fe * cfg.num_shared_experts
    dense_layers = dict(
        mla.param_shapes(cfg, dense, dt),
        mlp_norm=SDS((dense, d), dt), w_gate=SDS((dense, d, cfg.d_ff), dt),
        w_up=SDS((dense, d, cfg.d_ff), dt), w_down=SDS((dense, cfg.d_ff, d), dt))
    moe_layers = dict(
        mla.param_shapes(cfg, moe, dt),
        mlp_norm=SDS((moe, d), dt), router=SDS((moe, d, e), dt),
        e_gate=SDS((moe, e, d, fe), dt), e_up=SDS((moe, e, d, fe), dt),
        e_down=SDS((moe, e, fe, d), dt),
        s_gate=SDS((moe, d, fs), dt), s_up=SDS((moe, d, fs), dt),
        s_down=SDS((moe, fs, d), dt))
    return {"embed": SDS((vp, d), dt), "final_norm": SDS((d,), dt),
            "out_head": SDS((d, vp), dt), "dense_layers": dense_layers,
            "moe_layers": moe_layers}


def init_params(cfg, key):
    """Matrices truncated-normal with std 0.02; norm offsets 0 (gain 1)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(cfg))
    keys = jax.random.split(key, len(flat))

    def mk(k, path, sds):
        if jax.tree_util.keystr(path).endswith("norm']"):
            return jnp.zeros(sds.shape, sds.dtype)
        return trunc_normal(k, sds.shape, 0.02, sds.dtype)

    return jax.tree.unflatten(treedef, [mk(k, p, s) for k, (p, s) in zip(keys, flat)])


def param_count(cfg) -> int:
    return sum(math.prod(s.shape) for s in jax.tree.leaves(param_shapes(cfg)))


def active_param_count(cfg) -> int:
    """Routed experts count k/E of their parameters."""
    moe = cfg.num_layers - cfg.first_dense_layers
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    return param_count(cfg) - moe * (cfg.num_experts - cfg.num_experts_per_tok) * per_expert


# --------------------------------------------------------------------------- #
# dropless routed experts                                                      #
# --------------------------------------------------------------------------- #
def routed_experts(cfg, lp, x, real):
    """x (T, D), real (T,) bool -> (y (T, D) float32, counts (E,) int32):
    each real token's weighted sum over its top-k experts, and how many
    real assignments each expert computed."""
    t, d = x.shape
    e, k, bm = cfg.num_experts, cfg.num_experts_per_tok, EXPERT_BLOCK_ROWS
    logits = jnp.dot(x.astype(jnp.float32), lp["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    # the model has one kernel switch: attention_impl picks XLA or Pallas for
    # every kernel of the forward (flash attention, router, expert FFN)
    weights, idx = ops.moe_topk_router(logits, k, renormalize=cfg.router_renormalize,
                                       impl=cfg.attention_impl,
                                       block_t=math.gcd(t, 1024))

    # assignments of padded positions go to expert e: nowhere
    flat_e = jnp.where(real[:, None], idx, e).reshape(-1)          # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    bounds = jnp.searchsorted(sorted_e, jnp.arange(e + 1, dtype=flat_e.dtype)
                              ).astype(jnp.int32)
    starts, counts = bounds[:e], jnp.diff(bounds)                   # (E,)
    offsets = kexp.padded_offsets(counts, bm)                      # (E+1,)
    safe_e = jnp.minimum(sorted_e, e - 1)
    # R rows hold every assignment and each group's last partial tile
    r = -(-t * k // bm) * bm + e * bm
    dest = jnp.where(sorted_e < e,
                     offsets[safe_e] + jnp.arange(t * k, dtype=jnp.int32) - starts[safe_e],
                     r)
    token_of_row = jnp.full((r,), t, jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    rows = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[token_of_row]
    y_rows = ops.expert_ffn(rows, lp["e_gate"], lp["e_up"], lp["e_down"], offsets,
                            block_rows=bm, impl=cfg.attention_impl)

    row_of = jnp.zeros((t * k,), jnp.int32).at[order].set(jnp.minimum(dest, r - 1))
    valid = (flat_e < e)[:, None]
    y = jnp.where(valid, y_rows[row_of], 0).astype(jnp.float32).reshape(t, k, d)
    return (y * weights.astype(jnp.float32)[..., None]).sum(1), counts


def expert_counts(expert_hist, block_rows: int = EXPERT_BLOCK_ROWS) -> Dict[str, int]:
    """What a batch's (MoE layers, E) histogram of real assignments counts:
    assignments, rows the expert kernel computed (each expert's last
    partial tile included), and the largest expert's load summed over the
    layers."""
    h = np.asarray(expert_hist, np.int64)
    return {"expert_assignments": int(h.sum()),
            "expert_rows_launched": int((-(-h // block_rows) * block_rows).sum()),
            "expert_load_peak": int(h.max(-1).sum()) if h.size else 0}


# --------------------------------------------------------------------------- #
# forward                                                                      #
# --------------------------------------------------------------------------- #
def _attend(cfg, lp, h, positions):
    return h + mla.attention(cfg, lp, rms_norm(h, lp["attn_norm"], cfg.norm_eps),
                             positions)


def forward(cfg, params, batch, ctx: ShardCtx = NULL_CTX):
    """-> (logits (B, S, V), {"expert_hist": (MoE layers, E) int32}), the
    histogram of the batch's real assignments per MoE layer."""
    tokens = batch["tokens"]
    real = batch.get("token_mask")
    real = jnp.ones(tokens.shape, bool) if real is None else real
    h, positions = tf.embed_input(cfg, params, batch, ctx)
    b, s, d = h.shape

    def dense_layer(h, lp):
        h = _attend(cfg, lp, h, positions)
        m = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        return h + swiglu_mlp(m, lp["w_gate"], lp["w_up"], lp["w_down"], ctx), None

    def moe_layer(h, lp):
        h = _attend(cfg, lp, h, positions)
        m = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        routed, counts = routed_experts(cfg, lp, m.reshape(b * s, d), real.reshape(-1))
        y = routed.reshape(b, s, d).astype(m.dtype)
        y = y + swiglu_mlp(m, lp["s_gate"], lp["s_up"], lp["s_down"], ctx)
        return h + y, counts

    h, _ = jax.lax.scan(dense_layer, h, params["dense_layers"])
    h, hist = jax.lax.scan(moe_layer, h, params["moe_layers"])
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return lm_logits(h, params["out_head"], cfg.vocab_size, ctx), {"expert_hist": hist}
