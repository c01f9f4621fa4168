"""Compile every Pallas kernel for a described TPU v5e chip, at the widths
of the model configs that use it, with no chip attached.

Interpret mode accepts block shapes and in-kernel ops that the TPU compiler
refuses (the (8, 128) tiling rule, gathers, 1-D blocks), so these compiles
are what keeps a kernel runnable on the chip. The topology is described
inside a fixture, never at import: only the worker that runs this file may
load the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.deepseek_v2_lite import CONFIG as DEEPSEEK
from repro.kernels.decode_attention import decode_attention_bkgd
from repro.kernels.expert_ffn import expert_ffn_sorted
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.hsv_color import hsv_color_hist
from repro.kernels.moe_router import moe_router_tk
from repro.kernels.ref import COLOR_RANGES
from repro.kernels.rglru import rglru_bsw
from repro.kernels.ssd import ssd_bhcp
from repro.launch.serve import MAX_LEN
from repro.udfs.library import block_divisor

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _smollm():
    c = get_config("smollm-135m")
    return c.num_heads, c.num_kv_heads, c.head_dim


def _hsv():
    size = 224  # the classifier crop
    return (lambda c, r: hsv_color_hist(
                c, r, block_rows=block_divisor(size, 64), interpret=False),
            [((16, size, size, 3), F32), (COLOR_RANGES.shape, F32)])


def _flash():
    h, hkv, d = _smollm()
    b = 16  # the LLM UDF's row bucket
    return (lambda q, k, v: flash_attention_bhsd(
                q, k, v, group=h // hkv, interpret=False),
            [((b * h, MAX_LEN, d), BF16), ((b * hkv, MAX_LEN, d), BF16),
             ((b * hkv, MAX_LEN, d), BF16)])


def _decode():
    h, hkv, d = _smollm()
    b, s = 8, 2048
    return (lambda q, k, v, n: decode_attention_bkgd(
                q, k, v, n, num_kv_heads=hkv, block_k=256, interpret=False),
            [((b * hkv, h // hkv, d), BF16), ((b * hkv, s, d), BF16),
             ((b * hkv, s, d), BF16), ((b,), I32)])


def _rglru():
    w = get_config("recurrentgemma-9b").d_model  # LRU width == d_model
    b, s = 2, 1024
    return (lambda x, r, i, a, h: rglru_bsw(
                x, r, i, a, h, block_s=256, block_w=512, interpret=False),
            [((b, s, w), F32)] * 3 + [((w,), F32), ((b, w), F32)])


def _ssd():
    c = get_config("mamba2-370m")
    b, s, h, p, n = 2, 1024, c.ssm_heads, c.ssm_head_dim, c.ssm_state
    g = c.ssm_groups
    return (lambda x, dt, a, bm, cm, h0: ssd_bhcp(
                x, dt, a, bm, cm, h0, chunk=64, interpret=False),
            [((b, h, s, p), F32), ((b, h, s), F32), ((h,), F32),
             ((b, g, s, n), F32), ((b, g, s, n), F32), ((b, h, p, n), F32)])


def _moe_router():
    c = get_config("arctic-480b")
    return (lambda lg: moe_router_tk(lg, c.num_experts_per_tok,
                                     interpret=False),
            [((4096, c.num_experts), F32)])


def _flash_mla():
    c = DEEPSEEK
    b, qk = 16, c.qk_nope_head_dim + c.qk_rope_head_dim
    bh = b * c.num_heads
    return (lambda q, k, v: flash_attention_bhsd(
                q, k, v, group=1, scale=qk ** -0.5, interpret=False),
            [((bh, MAX_LEN, qk), BF16), ((bh, MAX_LEN, qk), BF16),
             ((bh, MAX_LEN, c.v_head_dim), BF16)])


def _expert_ffn():
    c, bm = DEEPSEEK, 128
    e, d, f = c.num_experts, c.d_model, c.moe_d_ff
    r = 16 * MAX_LEN * c.num_experts_per_tok + e * bm  # the scorer's static rows
    return (lambda x, g, u, w, off: expert_ffn_sorted(
                x, g, u, w, off, block_rows=bm, interpret=False),
            [((r, d), BF16), ((e, d, f), BF16), ((e, d, f), BF16),
             ((e, f, d), BF16), ((e + 1,), I32)])


def _moe_router_k6():
    c = DEEPSEEK
    return (lambda lg: moe_router_tk(lg, c.num_experts_per_tok, renormalize=False,
                                     interpret=False),
            [((16 * MAX_LEN, c.num_experts), F32)])


@pytest.mark.parametrize("case", [_hsv, _flash, _decode, _rglru, _ssd,
                                  _moe_router, _flash_mla, _expert_ffn,
                                  _moe_router_k6],
                         ids=lambda f: f.__name__.strip("_"))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = case()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
