"""Synthetic predicates for deterministic benchmarks and examples.

Two families, both previously duplicated as ad-hoc closures across
examples/ and benchmarks/:

* ``planted_predicate`` — a pure membership filter with an ANALYTIC cost
  model (rows * cost_per_row). This is the SimClock workhorse: the UC1/UC3
  benchmarks plant ground-truth pass sets and paper-calibrated per-row
  costs, then compare routing policies on simulated makespan.

* ``planted_detector`` / ``planted_classifier`` — REAL compute (the HSV
  color kernel over the pixel column, so wall-clock cost is genuine) with
  planted labels, standing in for detector/classifier checkpoints we don't
  ship. The detector reads boolean labels indexed by ``rid``; the
  classifier reads integer labels from a batch column and passes
  ``label == target``.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

from repro.core.statstore import canonical_fingerprint
from repro.core.udf import LAUNCH_SPAN, Predicate, UDF
from repro.kernels import launch as kernel_launch
from repro.kernels import ops
from repro.udfs.library import block_divisor, one_row_probe
from repro.udfs import rooflines


def planted_predicate(
    name: str,
    passing_ids: Iterable[int],
    *,
    cost_per_row: float,
    resource: str = "tpu:0",
    column: str = "rid",
) -> Predicate:
    """Membership filter over ``column`` with an analytic SimClock cost."""
    ids = np.asarray(sorted(int(i) for i in passing_ids))

    udf = UDF(
        name,
        fn=lambda d: np.isin(d[column], ids),
        columns=(column,),
        resource=resource,
        cost_model=lambda rows: rows * cost_per_row,
        bucket=False,
        # planted sets are benchmark-local, so the fingerprint keys on the
        # planted NAME + cost config: re-building the same scenario in a
        # fresh process maps to the same persistent-statistics record
        fingerprint=canonical_fingerprint(
            f"planted:{name}", cost_per_row=cost_per_row, column=column),
    )
    return Predicate(name, udf, compare=lambda o: o.astype(bool))


def planted_detector(
    name: str,
    planted_mask: np.ndarray,
    *,
    work_dim: int = 96,
    impl: str = "pallas",
    resource: str = "tpu:0",
) -> Predicate:
    """Detector stand-in: real HSV-kernel compute + planted boolean labels.

    The ``frame`` column supplies the pixels (any layout reshapeable to
    (rows, work_dim, work_dim, 3)); ``rid`` indexes the planted labels.
    With the default ``impl="pallas"`` every evaluation is a real kernel
    launch, so an executor's launch hook sees genuine per-launch cost."""
    planted = np.asarray(planted_mask).astype(bool)
    block_rows = block_divisor(work_dim, 64)

    def fn(d):
        ops.hsv_color_classify(
            np.asarray(d["frame"], np.float32).reshape(
                -1, work_dim, work_dim, 3
            ),
            impl=impl, block_rows=block_rows,
        )
        return planted[d["rid"]]

    udf = UDF(
        name, fn, columns=("frame", "rid"), resource=resource, bucket=False,
        warm_fn=one_row_probe(
            fn, {"frame": (work_dim, work_dim, 3), "rid": ()},
            {"frame": np.float32, "rid": np.int64},
        ),
        cost_model=rooflines.hsv_color(work_dim, work_dim).cost_model,
        proxy_cost=lambda d: float(np.asarray(d["frame"]).size),
    )
    return Predicate(name, udf, compare=lambda o: o.astype(bool))


def planted_classifier(
    name: str,
    target: int,
    *,
    label_column: str,
    pixel_column: str = "crop",
    impl: str = "xla",
    resource: str = "tpu:0",
) -> Predicate:
    """Classifier stand-in: real HSV compute over (B, H, W, 3) pixels +
    planted integer labels read from ``label_column``; passes label ==
    ``target``. ``impl="xla"`` burns real XLA compute without claiming a
    kernel launch (a ViT stand-in, not the color kernel itself)."""

    def fn(d):
        px = np.asarray(d[pixel_column], np.float32)
        with kernel_launch.span(LAUNCH_SPAN):
            ops.hsv_color_classify(px, impl=impl,
                                   block_rows=block_divisor(px.shape[1], 64))
        return np.asarray(d[label_column])

    udf = UDF(
        name, fn, columns=(pixel_column, label_column), resource=resource,
        proxy_cost=lambda d: float(np.asarray(d[pixel_column]).size),
    )
    return Predicate(name, udf, compare=lambda o: o == target)
