"""Kernel-backed predicate builders: one per Pallas kernel in the repo.

Every builder returns a first-class ``Predicate`` whose UDF

  * launches the real kernel through ``repro.kernels.launch.pallas_call``
    (compiled on TPU, interpreter elsewhere) so per-launch timings flow
    into the executor's StatsBoard via ``connect_stats_board``;
  * pre-compiles in ``warm_fn`` — GACU lazy activation (§5.1): the first
    batch routed to a worker pays compile cost, not every policy probe;
  * carries a roofline-derived ``cost_model`` prior
    (``repro.udfs.rooflines``) for SimClock runs and cold-start ranking;
  * declares a data-aware ``proxy_cost`` (crop pixels / live tokens) for
    the Laminar data-balancing policy;
  * keeps ``bucket=True`` so row counts quantize to powers of two and a
    handful of executables serve any batch (§5.1's recompilation answer);
  * carries a canonical ``fingerprint`` (kernel name + every config knob
    that changes the predicate's decision, incl. the compare target, +
    cost-model version — ``core/statstore.canonical_fingerprint``) so the
    persistent StatsStore warm-starts the same predicate across processes
    and never conflates two configurations of one kernel.

Text-consuming kernels (moe_router, ssd, rglru, flash/decode attention)
share a deterministic seeded featurizer: token ids index fixed embedding
tables (row 0 = padding = zeros), so the predicate is a pure function of
the ``tokens`` column and an oracle can re-evaluate it exactly.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax.numpy as jnp
import numpy as np

from repro.core.statstore import canonical_fingerprint
from repro.core.udf import D2H_SPAN, H2D_SPAN, LAUNCH_SPAN, Predicate, UDF
from repro.kernels import launch as kernel_launch
from repro.kernels import ops, ref
from repro.udfs import rooflines


# --------------------------------------------------------------------------- #
# featurizer helpers                                                          #
# --------------------------------------------------------------------------- #
def _embed_table(rng: np.random.Generator, vocab: int, dim: int) -> jnp.ndarray:
    """Fixed random embedding table; row 0 (padding) embeds to zero."""
    t = rng.standard_normal((vocab, dim)).astype(np.float32) / np.sqrt(dim)
    t[0] = 0.0
    return jnp.asarray(t)


def _pad_tokens(tokens: np.ndarray, seq: int) -> np.ndarray:
    """(B, L) int tokens -> (B, seq): truncate or zero-pad the time axis."""
    toks = np.asarray(tokens)
    b, length = toks.shape
    if length == seq:
        return toks.astype(np.int32)
    out = np.zeros((b, seq), np.int32)
    out[:, : min(length, seq)] = toks[:, :seq]
    return out


def block_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= cap (kernel block constraint)."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def _token_proxy(d: Dict[str, np.ndarray]) -> float:
    """Data-aware load: live (non-pad) tokens, the paper's input-size proxy."""
    return float((np.asarray(d["tokens"]) > 0).sum())


def one_row_probe(fn: Callable, columns: Dict[str, tuple],
                  dtypes: Dict[str, np.dtype]) -> Callable[[], object]:
    """GACU ``warm_fn``: run the kernel once on a single synthesized row.

    Returns the probe output so ``UDF.ensure_ready`` learns the output
    dtype/shape from the warm launch — zero-row batches then need no probe
    launch of their own."""

    def warm():
        return fn(
            {c: np.zeros((1,) + shape, dtypes[c])
             for c, shape in columns.items()}
        )

    return warm


# --------------------------------------------------------------------------- #
# builders                                                                    #
# --------------------------------------------------------------------------- #
def color_predicate(
    color: str = "black",
    *,
    size: int = 64,
    impl: str = "pallas",
    resource: str = "tpu:0",
    name: str = None,
) -> Predicate:
    """HSV color classifier over ``crop`` (B, size, size, 3) RGB [0,255].

    The paper's DogColorClassifier: kernel-fused RGB->HSV + range bucketing
    + histogram argmax; passes rows whose dominant color == ``color``."""
    target = ref.COLOR_NAMES.index(color)
    block_rows = block_divisor(size, 64)

    def fn(d):
        with kernel_launch.span(H2D_SPAN):
            crops = jnp.asarray(np.asarray(d["crop"], np.float32))
        with kernel_launch.span(LAUNCH_SPAN):
            _, label = ops.hsv_color_classify(crops, impl=impl,
                                              block_rows=block_rows)
        with kernel_launch.span(D2H_SPAN):
            return np.asarray(label)

    name = name or f"color_is_{color}"
    udf = UDF(
        name, fn, columns=("crop",), resource=resource,
        warm_fn=one_row_probe(fn, {"crop": (size, size, 3)},
                               {"crop": np.float32}),
        cost_model=rooflines.hsv_color(size, size).cost_model,
        proxy_cost=lambda d: float(np.asarray(d["crop"]).size),
        fingerprint=canonical_fingerprint(
            "hsv_color", color=color, size=size, impl=impl),
    )
    return Predicate(name, udf, compare=lambda o: o == target)


def topic_router_predicate(
    expert: int = 0,
    *,
    n_experts: int = 8,
    k: int = 2,
    dim: int = 16,
    vocab: int = 256,
    seq: int = 64,
    seed: int = 0,
    impl: str = "pallas",
    resource: str = "tpu:0",
    name: str = None,
) -> Predicate:
    """MoE top-k gate over mean-pooled token embeddings (``tokens`` column).

    Passes rows whose top-1 expert == ``expert`` — content routing as a
    predicate, with the fused moe_router kernel doing the gating."""
    rng = np.random.default_rng(seed)
    emb = _embed_table(rng, vocab, dim)
    w_gate = jnp.asarray(
        rng.standard_normal((dim, n_experts)).astype(np.float32) / np.sqrt(dim)
    )

    def fn(d):
        toks = _pad_tokens(d["tokens"], seq)
        x = emb[jnp.asarray(toks)]                          # (B, S, dim)
        live = jnp.maximum((jnp.asarray(toks) > 0).sum(1, keepdims=True), 1)
        logits = (x.sum(1) / live) @ w_gate                 # (B, E)
        _, idx = ops.moe_topk_router(logits, k, impl=impl)
        return np.asarray(idx[:, 0])

    name = name or f"routes_to_expert{expert}"
    udf = UDF(
        name, fn, columns=("tokens",), resource=resource,
        warm_fn=one_row_probe(fn, {"tokens": (seq,)}, {"tokens": np.int32}),
        cost_model=rooflines.moe_router(n_experts, k).cost_model,
        proxy_cost=_token_proxy,
        fingerprint=canonical_fingerprint(
            "moe_router", expert=expert, n_experts=n_experts, k=k, dim=dim,
            vocab=vocab, seq=seq, seed=seed, impl=impl),
    )
    return Predicate(name, udf, compare=lambda o: o == expert)


def ssd_scorer_predicate(
    threshold: float = 0.0,
    *,
    seq: int = 64,
    heads: int = 2,
    head_dim: int = 4,
    state: int = 4,
    vocab: int = 256,
    seed: int = 1,
    impl: str = "pallas",
    resource: str = "tpu:0",
    name: str = None,
) -> Predicate:
    """Mamba-2 SSD sequence scorer over ``tokens``; passes score > threshold.

    Token embeddings drive x/B/C; dt gates off padding (dt=0 there, so pads
    never update the state). Score = mean of the scanned output."""
    rng = np.random.default_rng(seed)
    emb_x = _embed_table(rng, vocab, heads * head_dim)
    emb_b = _embed_table(rng, vocab, state)
    emb_c = _embed_table(rng, vocab, state)
    A = -np.abs(rng.standard_normal(heads)).astype(np.float32)
    chunk = block_divisor(seq, 64)

    def fn(d):
        toks = _pad_tokens(d["tokens"], seq)
        jt = jnp.asarray(toks)
        b = toks.shape[0]
        x = emb_x[jt].reshape(b, seq, heads, head_dim)
        dt = jnp.repeat(((jt > 0) * 0.1).astype(jnp.float32)[..., None],
                        heads, axis=-1)                     # (B, S, H)
        Bm = emb_b[jt].reshape(b, seq, 1, state)
        Cm = emb_c[jt].reshape(b, seq, 1, state)
        y, _ = ops.ssd(x, dt, jnp.asarray(A), Bm, Cm, impl=impl, chunk=chunk)
        return np.asarray(y.mean(axis=(1, 2, 3)))

    name = name or "ssd_score_pos"
    udf = UDF(
        name, fn, columns=("tokens",), resource=resource,
        warm_fn=one_row_probe(fn, {"tokens": (seq,)}, {"tokens": np.int32}),
        cost_model=rooflines.ssd(seq, heads, head_dim, state).cost_model,
        proxy_cost=_token_proxy,
        fingerprint=canonical_fingerprint(
            "ssd", threshold=threshold, seq=seq, heads=heads,
            head_dim=head_dim, state=state, vocab=vocab, seed=seed,
            impl=impl),
    )
    return Predicate(name, udf, compare=lambda o: o > threshold)


def rglru_gate_predicate(
    threshold: float = 0.0,
    *,
    seq: int = 64,
    width: int = 16,
    vocab: int = 256,
    seed: int = 2,
    impl: str = "pallas",
    resource: str = "tpu:0",
    name: str = None,
) -> Predicate:
    """RG-LRU recurrent scorer over ``tokens``: final-state mean > threshold."""
    rng = np.random.default_rng(seed)
    emb_x = _embed_table(rng, vocab, width)
    emb_r = _embed_table(rng, vocab, width)
    emb_i = _embed_table(rng, vocab, width)
    a_param = jnp.asarray(rng.standard_normal(width).astype(np.float32))
    block_s = block_divisor(seq, 256)

    def fn(d):
        toks = _pad_tokens(d["tokens"], seq)
        jt = jnp.asarray(toks)
        _, h_last = ops.rglru(emb_x[jt], emb_r[jt], emb_i[jt], a_param,
                              impl=impl, block_s=block_s)
        return np.asarray(h_last.mean(-1))

    name = name or "rglru_gate_pos"
    udf = UDF(
        name, fn, columns=("tokens",), resource=resource,
        warm_fn=one_row_probe(fn, {"tokens": (seq,)}, {"tokens": np.int32}),
        cost_model=rooflines.rglru(seq, width).cost_model,
        proxy_cost=_token_proxy,
        fingerprint=canonical_fingerprint(
            "rglru", threshold=threshold, seq=seq, width=width, vocab=vocab,
            seed=seed, impl=impl),
    )
    return Predicate(name, udf, compare=lambda o: o > threshold)


def attention_scorer_predicate(
    threshold: float = 0.0,
    *,
    seq: int = 32,
    heads: int = 2,
    head_dim: int = 8,
    vocab: int = 256,
    seed: int = 3,
    impl: str = "pallas",
    resource: str = "tpu:0",
    name: str = None,
) -> Predicate:
    """Causal flash-attention scorer over ``tokens``: output mean > threshold."""
    rng = np.random.default_rng(seed)
    emb_q = _embed_table(rng, vocab, heads * head_dim)
    emb_k = _embed_table(rng, vocab, heads * head_dim)
    emb_v = _embed_table(rng, vocab, heads * head_dim)

    def fn(d):
        toks = _pad_tokens(d["tokens"], seq)
        jt = jnp.asarray(toks)
        b = toks.shape[0]
        shape = (b, seq, heads, head_dim)
        out = ops.flash_attention(
            emb_q[jt].reshape(shape), emb_k[jt].reshape(shape),
            emb_v[jt].reshape(shape),
            causal=True, impl=impl, block_q=seq, block_k=seq,
        )
        return np.asarray(out.mean(axis=(1, 2, 3)))

    name = name or "attn_score_pos"
    udf = UDF(
        name, fn, columns=("tokens",), resource=resource,
        warm_fn=one_row_probe(fn, {"tokens": (seq,)}, {"tokens": np.int32}),
        cost_model=rooflines.flash_attention(seq, heads, head_dim).cost_model,
        proxy_cost=_token_proxy,
        fingerprint=canonical_fingerprint(
            "flash_attention", threshold=threshold, seq=seq, heads=heads,
            head_dim=head_dim, vocab=vocab, seed=seed, impl=impl),
    )
    return Predicate(name, udf, compare=lambda o: o > threshold)


def decode_relevance_predicate(
    threshold: float = 0.0,
    *,
    seq: int = 32,
    heads: int = 2,
    head_dim: int = 8,
    kv_heads: int = 1,
    vocab: int = 256,
    seed: int = 4,
    impl: str = "pallas",
    resource: str = "tpu:0",
    name: str = None,
) -> Predicate:
    """Decode-attention relevance over ``tokens``: a fixed query attends the
    row's token KV cache (true lengths mask padding); mean > threshold."""
    rng = np.random.default_rng(seed)
    emb_k = _embed_table(rng, vocab, kv_heads * head_dim)
    emb_v = _embed_table(rng, vocab, kv_heads * head_dim)
    query = jnp.asarray(
        rng.standard_normal((heads, head_dim)).astype(np.float32)
    )

    def fn(d):
        toks = _pad_tokens(d["tokens"], seq)
        jt = jnp.asarray(toks)
        b = toks.shape[0]
        kc = emb_k[jt].reshape(b, seq, kv_heads, head_dim)
        vc = emb_v[jt].reshape(b, seq, kv_heads, head_dim)
        q = jnp.broadcast_to(query, (b, heads, head_dim))
        lengths = jnp.asarray(
            np.maximum((toks > 0).sum(1), 1).astype(np.int32)
        )
        out = ops.decode_attention(q, kc, vc, lengths, impl=impl, block_k=seq)
        return np.asarray(out.mean(axis=(1, 2)))

    name = name or "decode_relevance_pos"
    udf = UDF(
        name, fn, columns=("tokens",), resource=resource,
        warm_fn=one_row_probe(fn, {"tokens": (seq,)}, {"tokens": np.int32}),
        cost_model=rooflines.decode_attention(
            seq, heads, head_dim, kv_heads).cost_model,
        proxy_cost=_token_proxy,
        fingerprint=canonical_fingerprint(
            "decode_attention", threshold=threshold, seq=seq, heads=heads,
            head_dim=head_dim, kv_heads=kv_heads, vocab=vocab, seed=seed,
            impl=impl),
    )
    return Predicate(name, udf, compare=lambda o: o > threshold)


# --------------------------------------------------------------------------- #
# registry                                                                    #
# --------------------------------------------------------------------------- #
# kernel launch name (what StatsBoard entries report under) -> builder
KERNEL_PREDICATES: Dict[str, Callable[..., Predicate]] = {
    "hsv_color": color_predicate,
    "moe_router": topic_router_predicate,
    "ssd": ssd_scorer_predicate,
    "rglru": rglru_gate_predicate,
    "flash_attention": attention_scorer_predicate,
    "decode_attention": decode_relevance_predicate,
}


def register_kernel_predicate(kernel: str,
                              builder: Callable[..., Predicate]) -> None:
    """Register a builder under its kernel's launch name (see __init__)."""
    if kernel in KERNEL_PREDICATES:
        raise ValueError(f"kernel predicate {kernel!r} already registered")
    KERNEL_PREDICATES[kernel] = builder


def build_predicate(kernel: str, **kwargs) -> Predicate:
    """Instantiate the registered builder for ``kernel``."""
    try:
        builder = KERNEL_PREDICATES[kernel]
    except KeyError:
        raise KeyError(
            f"no kernel predicate registered for {kernel!r}; "
            f"known: {sorted(KERNEL_PREDICATES)}"
        ) from None
    return builder(**kwargs)
