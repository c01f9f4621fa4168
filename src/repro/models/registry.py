"""Family registry: maps ModelConfig.family -> implementation module.

Every module satisfies the uniform API:
  param_shapes, param_logical, init_params, param_count, active_param_count,
  loss_fn, make_train_step, prefill, decode_step, input_specs, cache_shapes,
  roofline_units
except ``deepseek``, which has the forward pass a scorer needs:
  param_shapes, init_params, param_count, active_param_count, forward
"""
from __future__ import annotations

from types import ModuleType


def family_module(family: str) -> ModuleType:
    from repro.models import deepseek, encdec, hybrid, moe, ssm, transformer, vlm

    table = {
        "dense": transformer,
        "moe": moe,
        "deepseek": deepseek,
        "encdec": encdec,
        "hybrid": hybrid,
        "ssm": ssm,
        "vlm": vlm,
    }
    if family not in table:
        raise KeyError(f"unknown family {family!r}")
    return table[family]


def model_api(cfg) -> ModuleType:
    return family_module(cfg.family)
