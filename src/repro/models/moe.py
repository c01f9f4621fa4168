"""MoE decoder family (arctic-480b, grok-1-314b): GQA attention, experts
behind a capacity-bounded dispatch, for training, prefill and decode.

DeepSeek-V2 (``models/deepseek.py``) shares only the top-k routing
(``kernels/ref.moe_topk_router``, there without renormalisation); its
scorer needs every assignment computed, so it dispatches droplessly
through the grouped expert kernel instead of the capacity below.

Dispatch design (DESIGN.md §5): activations are TP-replicated across the
"model" axis, so expert dispatch needs NO all-to-all — a shard_map over
"model" lets each shard gather the (capacity-bounded) tokens routed to its
local experts, compute, scatter-add, and contribute through the same psum a
dense TP MLP needs anyway. Two layouts fall out of the sharding rules
automatically:

  * EP  (arctic: 128 experts % 16 == 0): expert dim sharded -> each shard
    owns E/16 experts fully.
  * TP  (grok: 8 experts < 16-way axis): experts replicated, d_ff sharded ->
    each shard computes ALL experts on its f-slice; psum sums the partials.

Routing is the fused top-k kernel's math (kernels/moe_router.py; ref path
inside the shard_map so XLA cost analysis sees the FLOPs). A switch-style
load-balancing aux loss is added to the task loss.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as kref
from repro.models import transformer as tf
from repro.models.layers import NULL_CTX, ShardCtx, dtype_of, rms_norm, swiglu_mlp
from repro.distributed.sharding import spec_for

SDS = jax.ShapeDtypeStruct

AUX_LOSS_COEF = 0.01


# --------------------------------------------------------------------------- #
# parameters                                                                   #
# --------------------------------------------------------------------------- #
def layer_param_shapes(cfg) -> Dict[str, SDS]:
    shapes = tf.layer_param_shapes(cfg)
    L, d, f, e = cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = dtype_of(cfg)
    shapes.update(
        {
            "router": SDS((L, d, e), dt),
            "e_gate": SDS((L, e, d, f), dt),
            "e_up": SDS((L, e, d, f), dt),
            "e_down": SDS((L, e, f, d), dt),
        }
    )
    if not cfg.moe_dense_residual:
        # pure-MoE layers have no dense MLP
        for k in ("w_gate", "w_up", "w_down"):
            shapes.pop(k)
    return shapes


def layer_param_logical(cfg) -> Dict[str, str]:
    logical = tf.layer_param_logical(cfg)
    if getattr(cfg, "moe_serve_ep2d", False):
        # resident-expert serving layout: experts over 'data', d_ff over
        # 'model' — matches the ep2d shard_map in_specs EXACTLY so no
        # per-layer weight reshuffle is inserted (measured in SS Perf).
        logical.update(
            {
                "router": "layers d_model_w .",
                "e_gate": "layers experts_data . d_ff",
                "e_up": "layers experts_data . d_ff",
                "e_down": "layers experts_data d_ff .",
            }
        )
    else:
        logical.update(
            {
                # expert_dw shards over "data" in BOTH train (FSDP) and
                # serve rules: 480B of experts cannot be data-replicated at
                # serve; shard_map in_specs gather them per layer (moe_ffn).
                "router": "layers d_model_w .",
                "e_gate": "layers experts expert_dw d_ff",
                "e_up": "layers experts expert_dw d_ff",
                "e_down": "layers experts d_ff expert_dw",
            }
        )
    if not cfg.moe_dense_residual:
        for k in ("w_gate", "w_up", "w_down"):
            logical.pop(k)
    return logical


def param_shapes(cfg):
    out = tf.param_shapes(cfg)
    out["layers"] = layer_param_shapes(cfg)
    return out


def param_logical(cfg):
    out = tf.param_logical(cfg)
    out["layers"] = layer_param_logical(cfg)
    return out


input_specs = tf.input_specs
roofline_units = tf.roofline_units


def init_params(cfg, key):
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(key, len(flat))
    from repro.models.layers import trunc_normal

    def mk(k, sds):
        if sds.shape and len(sds.shape) >= 2:
            return trunc_normal(k, sds.shape, 0.02, sds.dtype)
        return jnp.zeros(sds.shape, sds.dtype)

    return jax.tree.unflatten(treedef, [mk(k, s) for k, s in zip(keys, flat)])


def param_count(cfg) -> int:
    return sum(math.prod(s.shape) for s in jax.tree.leaves(param_shapes(cfg)))


def active_param_count(cfg) -> int:
    """6*N_active*D accounting: experts count k/E of their params."""
    total = param_count(cfg)
    L, e, d, f = cfg.num_layers, cfg.num_experts, cfg.d_model, cfg.d_ff
    expert_params = L * e * 3 * d * f
    active_expert = L * cfg.num_experts_per_tok * 3 * d * f
    return total - expert_params + active_expert


# --------------------------------------------------------------------------- #
# MoE FFN                                                                      #
# --------------------------------------------------------------------------- #
def _capacity(cfg, tokens: int) -> int:
    c = math.ceil(cfg.num_experts_per_tok * tokens / cfg.num_experts * cfg.capacity_factor)
    return max(8, ((c + 7) // 8) * 8)


def _moe_local(x, router_w, wg, wu, wd, *, cfg, capacity, axis, ep: bool,
               expert_axis=None):
    """Per-shard MoE computation. x: (B_loc, S, D) replicated over `axis`.

    ``expert_axis``: mesh axis the EXPERT dim is sharded over (defaults to
    ``axis``); psum runs over ``axis`` which may be a tuple (the ep2d
    resident-expert layout psums over both 'data' and 'model')."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(t, d)

    logits = (xf @ router_w.astype(xf.dtype)).astype(jnp.float32)  # (T, E)
    weights, idx = kref.moe_topk_router(logits, k)

    # switch-style load-balance aux: E * sum(mean_prob_e * frac_tokens_e)
    probs = jax.nn.softmax(logits, axis=-1)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # rank of each assignment within its expert
    flat_e = idx.reshape(-1)                             # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = weights.astype(jnp.float32).reshape(-1)
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    order = jnp.argsort(flat_e, stable=True)
    rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[flat_e[order]]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted)

    keep = rank < capacity
    slot = jnp.where(keep, flat_e * capacity + rank, e * capacity)
    tok_per_slot = (
        jnp.full((e * capacity + 1,), t, jnp.int32).at[slot].set(flat_t)[: e * capacity]
    ).reshape(e, capacity)
    w_per_slot = (
        jnp.zeros((e * capacity + 1,), jnp.float32).at[slot].set(flat_w)[: e * capacity]
    ).reshape(e, capacity)

    # local expert slice
    e_loc = wg.shape[0]
    if ep and axis is not None:
        e0 = jax.lax.axis_index(expert_axis or axis) * e_loc
        tok_loc = jax.lax.dynamic_slice_in_dim(tok_per_slot, e0, e_loc, 0)
        w_loc = jax.lax.dynamic_slice_in_dim(w_per_slot, e0, e_loc, 0)
    else:
        tok_loc, w_loc = tok_per_slot, w_per_slot

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = xpad[tok_loc]                                      # (E_loc, C, D)
    g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
    ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(xe.dtype))
    ye = ye * w_loc[..., None].astype(ye.dtype)

    y = (
        jnp.zeros((t + 1, d), ye.dtype)
        .at[tok_loc.reshape(-1)]
        .add(ye.reshape(-1, d))[:t]
    )
    if axis is not None:
        y = jax.lax.psum(y, axis)
    return y.reshape(b, s, d), aux


def moe_ffn(cfg, lp, x, ctx: ShardCtx):
    """(B, S, D) -> ((B, S, D), aux_loss)."""
    e = cfg.num_experts
    model_size = ctx.axis_size("model")
    # capacity from the PER-DATA-SHARD token count (what each shard routes)
    dp = 1
    if ctx.mesh is not None:
        for a in ("pod", "data"):
            dp *= ctx.axis_size(a)
    b, s, _ = x.shape
    local_tokens = max(1, (b // max(dp, 1)) * s) if b >= dp else b * s
    capacity = _capacity(cfg, local_tokens)

    if ctx.mesh is None or model_size <= 1:
        return _moe_local(
            x, lp["router"], lp["e_gate"], lp["e_up"], lp["e_down"],
            cfg=cfg, capacity=capacity, axis=None, ep=False,
        )

    mesh = ctx.mesh
    rs = P(None, None)

    # ---- beyond-paper (§Perf): resident-expert 2D EP for small-token steps.
    # Experts shard over 'data' (128 % 16 == 0), d_ff over 'model': weights
    # are fully RESIDENT — no per-layer gather. Tokens replicate over the
    # mesh (cheap: decode moves B*D bytes, vs gathering GBs of weights);
    # disjoint expert contributions + partial-F products combine in one
    # psum over both axes.
    data_size = ctx.axis_size("data")
    tokens_global = b * s
    if (
        getattr(cfg, "moe_serve_ep2d", False)
        and data_size > 1
        and e % data_size == 0
        and tokens_global <= 4096
    ):
        cap2 = _capacity(cfg, tokens_global)
        fn = partial(_moe_local, cfg=cfg, capacity=cap2,
                     axis=("data", "model"), ep=True, expert_axis="data")
        y, aux = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(None, None, None), rs,
                      P("data", None, "model"), P("data", None, "model"),
                      P("data", "model", None)),
            out_specs=(P(None, None, None), P()),
            check_vma=False,
        )(x, lp["router"], lp["e_gate"], lp["e_up"], lp["e_down"])
        return ctx.constrain(y, "batch seq d_model"), aux

    ep = e % model_size == 0
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = ba if len(ba) > 1 else (ba[0] if ba else None)
    xs = P(bspec, None, None)
    if ep:
        ws_gu = P("model", None, None)
        ws_d = P("model", None, None)
    else:
        ws_gu = P(None, None, "model")
        ws_d = P(None, "model", None)
    fn = partial(_moe_local, cfg=cfg, capacity=capacity, axis="model", ep=ep)
    y, aux = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(xs, rs, ws_gu, ws_gu, ws_d),
        out_specs=(xs, P()),
        check_vma=False,
    )(x, lp["router"], lp["e_gate"], lp["e_up"], lp["e_down"])
    return y, aux


# --------------------------------------------------------------------------- #
# blocks / steps                                                               #
# --------------------------------------------------------------------------- #
def moe_block(cfg, lp, h, positions, ctx: ShardCtx, aux_acc=None):
    from repro.models import attention as attn

    a_in = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    a_out, _ = attn.attention_train(cfg, a_in, lp, positions, ctx,
                                    window=cfg.sliding_window)
    h = tf.sp_constrain(cfg, h + a_out, ctx)
    m_in = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    y, aux = moe_ffn(cfg, lp, m_in, ctx)
    if cfg.moe_dense_residual:
        y = y + swiglu_mlp(m_in, lp["w_gate"], lp["w_up"], lp["w_down"], ctx)
    return tf.sp_constrain(cfg, h + y, ctx), aux


def forward(cfg, params, batch, ctx: ShardCtx = NULL_CTX):
    h, positions = tf.embed_input(cfg, params, batch, ctx)

    def body(carry, lp):
        hh, aux_sum = carry
        hh, aux = moe_block(cfg, lp, hh, positions, ctx)
        return (hh, aux_sum + aux), None

    (h, aux_sum), _ = jax.lax.scan(
        tf._remat(cfg, body), (h, jnp.zeros((), jnp.float32)), params["layers"]
    )
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["out_head"]
    from repro.models.layers import lm_logits

    return lm_logits(h, head, cfg.vocab_size, ctx), aux_sum


def loss_fn(cfg, params, batch, ctx: ShardCtx = NULL_CTX):
    logits, aux = forward(cfg, params, batch, ctx)
    from repro.models.layers import softmax_xent

    task = softmax_xent(logits, batch["labels"], batch.get("loss_mask"))
    loss = task + AUX_LOSS_COEF * aux
    return loss, {"loss": task, "aux_loss": aux}


def make_train_step(cfg, optimizer, ctx: ShardCtx = NULL_CTX):
    return tf.make_train_step(cfg, optimizer, ctx, loss=loss_fn)


def _moe_mlp_fn(cfg, lp, m_in, ctx):
    y, _aux = moe_ffn(cfg, lp, m_in, ctx)
    if cfg.moe_dense_residual:
        y = y + swiglu_mlp(m_in, lp["w_gate"], lp["w_up"], lp["w_down"], ctx)
    return y


def prefill(cfg, params, batch, ctx: ShardCtx = NULL_CTX, pad_cache_to=None):
    from repro.models import attention as attn
    from repro.models.layers import lm_logits

    h, positions = tf.embed_input(cfg, params, batch, ctx)
    w = cfg.sliding_window

    def body(carry, lp):
        hh = carry
        a_in = rms_norm(hh, lp["attn_norm"], cfg.norm_eps)
        a_out, (k, v) = attn.attention_train(cfg, a_in, lp, positions, ctx, window=w)
        hh = hh + a_out
        m_in = rms_norm(hh, lp["mlp_norm"], cfg.norm_eps)
        hh = hh + _moe_mlp_fn(cfg, lp, m_in, ctx)
        k = ctx.constrain(k, "batch cache_seq kv_heads .")
        v = ctx.constrain(v, "batch cache_seq kv_heads .")
        return hh, (k, v)

    h, (ks, vs) = jax.lax.scan(tf._remat(cfg, body), h, params["layers"])
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["out_head"]
    logits = lm_logits(h[:, -1:], head, cfg.vocab_size, ctx)[:, 0]
    if pad_cache_to is not None and not w and pad_cache_to > ks.shape[2]:
        pad = pad_cache_to - ks.shape[2]
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": ks, "v": vs, "lengths": jnp.full((h.shape[0],), h.shape[1], jnp.int32)}
    return cache, logits


def decode_step(cfg, params, cache, batch, ctx: ShardCtx = NULL_CTX):
    return tf.decode_step(cfg, params, cache, batch, ctx, mlp_fn=_moe_mlp_fn)


cache_shapes = tf.cache_shapes
