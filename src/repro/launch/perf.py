import os
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Perf hillclimbing driver (§Perf): lower a cell under named variants and
report the roofline-term deltas + per-collective-type byte breakdowns.

Each iteration in EXPERIMENTS.md §Perf is one invocation:

  PYTHONPATH=src python -m repro.launch.perf --arch arctic-480b \
      --shape decode_32k --variant baseline --variant moe_ep2d

Variants are config transforms (the code paths they enable live in the
model zoo behind config flags, so production configs can adopt them).
Results accumulate in results/perf/<cell>__<variant>.json.
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.configs import get_config, get_shape  # noqa: E402
from repro.launch.dryrun import compile_cell, train_overrides  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chip_count  # noqa: E402
from repro.models.registry import model_api  # noqa: E402
from repro.roofline import analysis  # noqa: E402

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "perf")


# ----------------------------- variants ----------------------------------- #
def v_baseline(cfg):
    return cfg


def v_moe_ep2d(cfg):
    """Resident-expert 2D EP at serve: experts over 'data', d_ff over
    'model' — removes the per-layer expert weight gather entirely."""
    return dataclasses.replace(cfg, moe_serve_ep2d=True)


def v_cache_fp8(cfg):
    """KV cache stored in fp8_e4m3 (halves cache reads/writes)."""
    return dataclasses.replace(cfg, cache_dtype="float8_e4m3fn")


def v_remat_dots(cfg):
    return dataclasses.replace(cfg, remat_policy="dots_no_batch")


def v_accum16(cfg):
    return dataclasses.replace(cfg, grad_accum=16)


def v_accum4(cfg):
    return dataclasses.replace(cfg, grad_accum=4)


def v_sp_accum1(cfg):
    """Sequence-parallel activations + NO grad accumulation: the residual
    stream shards seq over 'model' (16x smaller), so the global batch fits
    in one pass and the per-microbatch FSDP weight regathers disappear."""
    return dataclasses.replace(cfg, seq_parallel=True, grad_accum=0)


def v_sp_accum2(cfg):
    return dataclasses.replace(cfg, seq_parallel=True, grad_accum=2)


def v_sp_accum4(cfg):
    return dataclasses.replace(cfg, seq_parallel=True, grad_accum=4)


def v_ep2d_fp8(cfg):
    """Stacked serving optimizations: resident experts + fp8 KV cache."""
    return dataclasses.replace(cfg, moe_serve_ep2d=True,
                               cache_dtype="float8_e4m3fn")


VARIANTS = {
    "baseline": v_baseline,
    "moe_ep2d": v_moe_ep2d,
    "cache_fp8": v_cache_fp8,
    "remat_dots": v_remat_dots,
    "accum16": v_accum16,
    "accum4": v_accum4,
    "sp_accum1": v_sp_accum1,
    "sp_accum2": v_sp_accum2,
    "sp_accum4": v_sp_accum4,
    "ep2d_fp8": v_ep2d_fp8,
}


def collective_breakdown(sample):
    out = {}
    for op, rec in sample.collectives.items():
        if rec["count"]:
            out[op] = {
                "count": rec["count"],
                "wire_GB": round(rec["wire_bytes"] / 1e9, 4),
            }
    return out


def run(arch: str, shape_name: str, variant: str, *, outdir: str,
        mesh_shape=None) -> dict:
    cfg0, shape = get_config(arch), get_shape(shape_name)
    cfg = VARIANTS[variant](cfg0)
    if mesh_shape is None:
        mesh = make_production_mesh(multi_pod=False)
    else:
        mesh = jax.make_mesh(
            tuple(mesh_shape), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )
    chips = mesh_chip_count(mesh)

    # full-cell compile (memory honesty: the REAL step, incl. accumulation)
    sample, times = compile_cell(cfg, shape, mesh)

    # TRUE-STEP accounting: a microbatched step repeats the whole pass —
    # including the FSDP weight gathers — per microbatch. Lower the pass at
    # the MICRO batch and scale by M (slight optimizer-update overcount,
    # documented in EXPERIMENTS.md).
    eff = train_overrides(cfg, shape)
    m = eff.grad_accum if (shape.kind == "train" and eff.grad_accum > 1) else 1
    pass_shape = (
        dataclasses.replace(shape, global_batch=shape.global_batch // m)
        if m > 1 else shape
    )
    api = model_api(cfg)
    base_cfg, units = api.roofline_units(cfg)
    base_cfg = dataclasses.replace(base_cfg, grad_accum=0)
    units = [(c, dataclasses.replace(u, grad_accum=0)) for c, u in units]
    base_s, _ = compile_cell(base_cfg, pass_shape, mesh)
    unit_s = [(c, compile_cell(u, pass_shape, mesh)[0]) for c, u in units]
    totals = analysis.delta_total(base_s, unit_s)
    totals = {k: v * m for k, v in totals.items()}
    terms = analysis.roofline_terms(totals["flops"], totals["bytes"], totals["wire"])
    terms["accum_factor"] = m
    mf = analysis.model_flops(cfg0, shape)

    rec = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh_shape": list(mesh.devices.shape),
        "terms": terms,
        "per_device": totals,
        "memory": sample.mem,
        "collectives_full_model_scan_once": collective_breakdown(sample),
        "model_flops": mf,
        "useful_ratio": mf / (totals["flops"] * chips) if totals["flops"] else 0,
        "times": times,
    }
    os.makedirs(outdir, exist_ok=True)
    tag = "" if mesh_shape is None else f"__mesh{'x'.join(map(str, mesh_shape))}"
    path = os.path.join(outdir, f"{arch}__{shape_name}__{variant}{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)

    print(f"== {arch} / {shape_name} / {variant} ==")
    print(f" compute_s={terms['compute_s']:.4g} memory_s={terms['memory_s']:.4g} "
          f"collective_s={terms['collective_s']:.4g} dominant={terms['dominant']}")
    print(f" roofline_fraction={terms['roofline_fraction']:.4f} "
          f"useful_ratio={rec['useful_ratio']:.3f}")
    print(f" temp_bytes/dev={sample.mem['temp_bytes']/1e9:.2f}GB "
          f"args/dev={sample.mem['argument_bytes']/1e9:.2f}GB")
    print(f" collectives (full model, scan-once): "
          f"{rec['collectives_full_model_scan_once']}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", action="append", default=None,
                    choices=sorted(VARIANTS))
    ap.add_argument("--mesh-shape", default=None,
                    help="override (data,model), e.g. 256,1 for pure DP")
    ap.add_argument("--outdir", default=os.path.abspath(RESULTS))
    args = ap.parse_args()
    ms = tuple(int(x) for x in args.mesh_shape.split(",")) if args.mesh_shape else None
    for v in args.variant or ["baseline"]:
        run(args.arch, args.shape, v, outdir=args.outdir, mesh_shape=ms)


if __name__ == "__main__":
    main()
