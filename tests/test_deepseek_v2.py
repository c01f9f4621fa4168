"""The DeepSeek-V2 family through the review scorer against the plain
float32 reference (``chipbench/refs/deepseek_v2.py``), at a toy size that
keeps every mechanism: one dense and two MoE layers, 8 routed experts of
which 3 per token, weights not renormalised, 2 shared experts, q/k 24 wide
against v 16 wide, YaRN rotary."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from chipbench.refs import deepseek_v2 as ref  # noqa: E402
from repro.configs.base import ModelConfig, YarnScaling  # noqa: E402
from repro.configs.deepseek_v2_lite import CONFIG  # noqa: E402
from repro.core import AQPExecutor, Predicate, make_batch  # noqa: E402
from repro.launch.serve import build_llm_udf, llm_score_fn, llm_scorer  # noqa: E402
from repro.models import deepseek  # noqa: E402

SIZES = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
         "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 3,
         "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 3,
         "first_k_dense_replace": 1, "vocab_size": 256, "rms_norm_eps": 1e-6,
         "rope_theta": 10000.0, "norm_topk_prob": False, "routed_scaling_factor": 1.0,
         "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
                          "mscale": 0.707, "mscale_all_dim": 0.707,
                          "original_max_position_embeddings": 4096}}
CFG = ModelConfig(
    name="deepseek-toy", family="deepseek", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, head_dim=24, d_ff=128, vocab_size=256, num_experts=8,
    num_experts_per_tok=3, moe_d_ff=32, num_shared_experts=2, first_dense_layers=1,
    router_renormalize=False, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    rope_scaling=YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6, dtype="float32", attention_impl="pallas")
LENGTHS = (5, 17, 40, 9)


def _weights(seed=3):
    """Float32 weights with nonzero norm gains, so the reference's (1 + w)
    convention is checked too."""
    w = ref.make_weights(SIZES, seed, dtype=jnp.float32)
    return jax.tree_util.tree_map_with_path(
        lambda p, x: x + 0.1 if jax.tree_util.keystr(p).endswith("norm']") else x, w)


def _rows(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).astype(np.int32) for n in LENGTHS]


def _padded(rows, width):
    toks = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    return toks


def _close(got, want):
    assert np.abs(np.asarray(got) - want).max() <= 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_udf_scores_match_reference(impl):
    w, rows = _weights(), _rows()
    udf = build_llm_udf(cfg=dataclasses.replace(CFG, attention_impl=impl), params=w)
    _close(udf({"tokens": _padded(rows, 64)}), ref.scores(w, rows, SIZES))


def test_padding_reaches_no_routed_expert():
    """The same rows padded to 64 and to 128 positions: the same scores and
    the same expert histogram, which holds the real tokens' assignments
    only (3 per real token in each MoE layer)."""
    w, rows = _weights(), _rows(1)
    score = llm_score_fn(CFG)
    s64, e64 = score(w, jnp.asarray(_padded(rows, 64)))
    s128, e128 = score(w, jnp.asarray(_padded(rows, 128)))
    np.testing.assert_allclose(np.asarray(s64), np.asarray(s128), rtol=1e-6, atol=1e-6)
    h64, h128 = np.asarray(e64["expert_hist"]), np.asarray(e128["expert_hist"])
    np.testing.assert_array_equal(h64, h128)
    assert h64.shape == (2, 8)
    assert (h64.sum(1) == 3 * sum(LENGTHS)).all()


def test_dropless_when_every_token_picks_the_same_experts():
    """A zero router ties every expert, so every real token takes experts
    0, 1 and 2: each of them holds all 71 real tokens (a full tile, no
    capacity) and the scores still match the reference."""
    w, rows = _weights(), _rows(2)
    w["moe_layers"]["router"] = jnp.zeros_like(w["moe_layers"]["router"])
    scores, extras = llm_score_fn(CFG)(w, jnp.asarray(_padded(rows, 64)))
    hist = np.asarray(extras["expert_hist"])
    assert (hist[:, :3] == sum(LENGTHS)).all() and (hist[:, 3:] == 0).all()
    _close(scores, ref.scores(w, rows, SIZES))


def test_fp8_control_departs_more_than_bf16_weights():
    w, rows = _weights(), _rows()
    f32 = ref.scores(w, rows, SIZES)
    bf16 = ref.scores(jax.tree.map(lambda p: p.astype(jnp.bfloat16), w), rows, SIZES)
    fp8 = ref.scores(w, rows, SIZES, dot=ref.fp8_dot)
    assert np.abs(fp8 - f32).max() > 3 * np.abs(bf16 - f32).max() > 0


def test_scorer_counts_expert_loads_into_its_statistics():
    """Four rows through an executor: the predicate's entry holds the
    real tokens' assignments, the rows the expert kernel computed (each
    expert's groups in whole 128-row tiles) and the peak loads."""
    w, rows = _weights(), _rows(3)
    toks = _padded(rows, 64)
    pred = Predicate("LLM_is_food", build_llm_udf(cfg=CFG, params=w),
                     compare=lambda s: s > 0)
    ex = AQPExecutor([pred], max_workers=1, warmup=False)
    ex.collect([make_batch({"tokens": toks}, np.arange(len(rows)))])
    entry = ex.stats_snapshot()["LLM_is_food"]
    _, extras = llm_score_fn(CFG)(w, jnp.asarray(toks))
    hist = np.asarray(extras["expert_hist"])
    assert entry["expert_assignments"] == 2 * 3 * sum(LENGTHS)
    assert entry["expert_rows_launched"] == 128 * int((hist > 0).sum())
    assert entry["expert_load_peak"] == int(hist.max(1).sum())
    assert entry["tokens_real"] == sum(LENGTHS)


def test_dense_scorer_keeps_returning_scores_alone():
    cfg = dataclasses.replace(CFG, family="dense", num_kv_heads=2, head_dim=16)
    from repro.models.registry import model_api

    params = model_api(cfg).init_params(cfg, jax.random.key(0))
    out = llm_scorer(cfg, params)(jnp.asarray(_padded(_rows(), 64)))
    assert out.shape == (len(LENGTHS),)
    assert build_llm_udf(cfg=cfg, params=params)({"tokens": _padded(_rows(), 64)}).shape \
        == (len(LENGTHS),)


def test_published_config_and_its_smoke_reduction():
    """The published widths, and a smoke config that keeps each mechanism."""
    c = CONFIG
    assert (c.num_layers, c.d_model, c.num_heads, c.vocab_size) == (27, 2048, 16, 102400)
    assert (c.num_experts, c.num_experts_per_tok, c.moe_d_ff, c.num_shared_experts,
            c.first_dense_layers, c.router_renormalize) == (64, 6, 1408, 2, 1, False)
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) \
        == (512, 128, 64, 128)
    assert c.rope_scaling.factor == 40 and c.rope_scaling.mscale_all_dim == 0.707
    # 1 dense layer of 81 M and 26 MoE layers of 584.8 M, embedding and head
    assert abs(c.param_count() - 15.71e9) < 0.01e9
    stage = dataclasses.replace(c, num_layers=5)
    assert abs(stage.param_count() - 2.84e9) < 0.01e9
    s = c.reduce_for_smoke()
    assert s.first_dense_layers == 1 and s.num_layers == 3
    assert s.qk_nope_head_dim + s.qk_rope_head_dim != s.v_head_dim
    params = deepseek.init_params(s, jax.random.key(1))
    scores, extras = llm_score_fn(dataclasses.replace(s, attention_impl="xla"))(
        params, jnp.asarray(_padded(_rows(), 64)))
    assert np.isfinite(np.asarray(scores)).all()
    assert np.asarray(extras["expert_hist"]).shape == (2, s.num_experts)
