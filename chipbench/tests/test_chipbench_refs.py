"""The plain float32 references against the program's CPU path at smoke
widths, and the controls against the references."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

from chipbench.data import reviews, video  # noqa: E402
from chipbench.refs import hsv, smollm  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref as kref  # noqa: E402
from repro.launch.serve import llm_scorer  # noqa: E402


def _smoke():
    cfg = get_config("smollm-135m").reduce_for_smoke()
    sizes = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
             "num_attention_heads": cfg.num_heads,
             "num_key_value_heads": cfg.num_kv_heads,
             "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
             "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}
    w = smollm.make_weights(sizes, 3, dtype=jnp.float32)
    # nonzero norm gains, so the reference's (1 + w) convention is checked
    w = jax.tree.map(lambda p: p + 0.1 if p.ndim <= 2 and p.shape[-1] == cfg.d_model
                     and p.shape[0] != cfg.vocab_size else p, w)
    params = dict(w, embed=jnp.pad(w["embed"], ((0, cfg.vocab_padded - cfg.vocab_size), (0, 0))))
    pool = reviews.make_pool(12, np.random.default_rng(0), max_len=128)
    return cfg, sizes, w, params, pool


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_smollm_reference_matches_program(impl):
    cfg, sizes, w, params, pool = _smoke()
    cfg = dataclasses.replace(cfg, attention_impl=impl)
    toks = pool.padded(0, len(pool))
    got = np.asarray(llm_scorer(cfg, params)(jnp.asarray(toks)))
    want = smollm.scores(w, [pool.row(i) for i in range(len(pool))], sizes)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_smollm_fp8_control_departs():
    cfg, sizes, w, params, pool = _smoke()
    rows = [pool.row(i) for i in range(len(pool))]
    f32 = smollm.scores(w, rows, sizes)
    bf16 = smollm.scores(jax.tree.map(lambda p: p.astype(jnp.bfloat16), w), rows, sizes)
    fp8 = smollm.scores(w, rows, sizes, dot=smollm.fp8_dot)
    assert np.abs(fp8 - f32).max() > 3 * np.abs(bf16 - f32).max() > 0


def test_hsv_reference_matches_program():
    rng = np.random.default_rng(4)
    crops = video.make_crops(["black", "gray", "yellow", "white"] * 2, 32, rng)
    frames = rng.integers(0, 256, (4, 32, 32, 3)).astype(np.float32)
    x = np.concatenate([crops, frames])
    want = hsv.histograms(x)
    for got in (kref.hsv_color_classify(jnp.asarray(x))[0],
                ops.hsv_color_classify(jnp.asarray(x), impl="pallas", block_rows=32)[0]):
        assert np.abs(np.asarray(got) - want).max() * 32 * 32 <= 1.0
    assert np.abs(hsv.control_histograms(x) - want).max() * 32 * 32 >= 3.0
