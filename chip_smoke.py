"""Bring-up smoke: the QueryService path on one TPU chip.

Two queries go through one ``QueryService`` (service -> executor -> eddy ->
Laminar worker -> UDF -> compiled Pallas/XLA), and each result is checked:

* Phase A, UC1 lost-dog query (``examples/lost_dog_query.py``): the planted
  breed classifier and the HSV color kernel over ``SyntheticVideo`` crops.
  The service's row-id multiset must equal a naive evaluation of both
  predicates over every row, and the kernel's histograms must match
  ``kernels/ref.py`` up to the pixels whose H, S or V lies within rounding
  of a range bound.
* Phase B, LLM review query (``repro.launch.serve``): SmolLM-135M at its
  published widths in bf16, random weights from ``--seed``, attention in the
  Pallas flash kernel. The service's rows must equal a direct evaluation of
  the same UDF, and the Pallas scores must match the XLA-attention scores
  (see ``phase_llm`` for the tolerance).

Every kernel launch must be compiled Pallas, and no query may retry,
degrade or pass rows through. Run it on a machine with one TPU chip:

  python3 chip_smoke.py

It exits non-zero, printing no result, when JAX finds no TPU. The last line
of standard output is ``{"ok": true, "device": {...}}``. The seconds it
prints are one cold run's smoke timings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.extend import core as jex_core  # noqa: E402

from repro import udfs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.plan import Query, TrivialPredicate, batches_of  # noqa: E402
from repro.core.policies import EDDY_POLICIES, DataAware  # noqa: E402
from repro.core.udf import Predicate  # noqa: E402
from repro.data.text import make_reviews  # noqa: E402
from repro.data.video import BREEDS, SyntheticVideo, crop_to_canonical  # noqa: E402
from repro.kernels import launch, ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    QueryService, build_llm_udf, llm_scorer, review_source,
)
from repro.models.registry import model_api  # noqa: E402
from repro.udfs.library import block_divisor  # noqa: E402

QUERY_TIMEOUT_S = 900.0
# A float32 result may sit this many ulps from the exact value (two
# divisions and a multiply on the way to H or S).
HSV_ULPS = 4


# --------------------------------------------------------------------------- #
# checks shared by both phases                                                #
# --------------------------------------------------------------------------- #
def run_query(service, predicates, source, **executor_kwargs):
    """Submit one query and wait for it; fail unless it completed with a
    clean fault ledger (no failure, retry, degrade, quarantine or
    pass-through verdict: a pass-through keeps rows and still succeeds)."""
    handle = service.submit(predicates, source, **executor_kwargs)
    report = handle.result(timeout=QUERY_TIMEOUT_S)
    if report.state != "DONE":
        raise RuntimeError(f"query {report.qid} ended {report.state}")
    f = report.faults
    dirty = {k: f[k] for k in ("failures", "retries", "passthrough_batches",
                               "skipped_routes", "quarantined", "degraded")
             if f[k]}
    if dirty:
        raise RuntimeError(f"query {report.qid} fault ledger not clean: {dirty}")
    return report


def check_backends(events, sites, expect: str) -> None:
    """Every observed launch and every traced kernel site ran on ``expect``."""
    seen = {e.backend for e in events} | {backend for _, backend in sites}
    if seen - {expect}:
        raise RuntimeError(
            f"kernel backends {sorted(seen)}, expected only {expect!r}")


def kernel_sites(fn, *args) -> collections.Counter:
    """(kernel name, backend) of every pallas_call in ``fn``'s jaxpr.

    Launch hooks see eager launches only; a kernel inside a jitted model
    shows up here instead."""
    sites = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                backend = "interpret" if eqn.params["interpret"] else "pallas"
                sites[(str(eqn.params["name"]), backend)] += 1
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    if isinstance(sub, jex_core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jex_core.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sites


def same_multiset(a, b) -> bool:
    return np.array_equal(np.sort(np.asarray(a)), np.sort(np.asarray(b)))


# --------------------------------------------------------------------------- #
# phase A: UC1 lost-dog query                                                 #
# --------------------------------------------------------------------------- #
def hsv_ambiguous_pixels(crops: np.ndarray) -> np.ndarray:
    """Per crop, the pixels whose H or S (computed in float64 as in
    ``ref.rgb_to_hsv``) comes from a division and lies within ``HSV_ULPS``
    float32 ulps of a range bound: two correct float32 lowerings may bucket
    exactly these differently. V, and an H or S of zero, are exact for
    integer RGB and always bucket alike."""
    rgb = np.asarray(crops, np.float64)
    r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
    mx, mn = rgb.max(-1), rgb.min(-1)
    diff = mx - mn
    safe = np.where(diff == 0, 1.0, diff)
    num = np.where(mx == r, g - b, np.where(mx == g, b - r, r - g))
    h = np.where(mx == r, np.mod(num / safe, 6.0),
                 num / safe + np.where(mx == g, 2.0, 4.0))
    h = np.where(diff == 0, 0.0, h) * 30.0
    s = np.where(mx == 0, 0.0, diff / np.where(mx == 0, 1.0, mx)) * 255.0
    near = np.zeros(mx.shape, bool)
    for x, cols, divided in ((h, [0, 3], (diff != 0) & (num != 0)),
                             (s, [1, 4], diff != 0)):
        ulp = np.spacing(np.abs(x).astype(np.float32)).astype(np.float64)
        eps = HSV_ULPS * ulp
        for bound in np.unique(ref.COLOR_RANGES[:, cols]):
            near |= divided & (np.abs(x - bound) <= eps)
    return near.sum(axis=(1, 2))


def check_hsv_against_ref(crops: np.ndarray, block_rows: int) -> dict:
    """Kernel histograms vs ``ref.hsv_color_classify`` on the same device.

    A pixel moves 1/(H*W) between two buckets when the two lowerings
    disagree, so bucket c of crop i may differ by at most
    (ambiguous_i + 0.5) / (H*W); labels must agree unless the reference's
    top two buckets are within twice that."""
    hk, lk = ops.hsv_color_classify(jnp.asarray(crops), impl="pallas",
                                    block_rows=block_rows)
    hr, lr = ref.hsv_color_classify(jnp.asarray(crops))
    hk, lk, hr, lr = map(np.asarray, (hk, lk, hr, lr))
    px = crops.shape[1] * crops.shape[2]
    amb = hsv_ambiguous_pixels(crops)
    tol = (amb + 0.5) / px
    err = np.abs(hk - hr).max(axis=1)
    if (err > tol).any():
        i = int(np.argmax(err - tol))
        raise RuntimeError(
            f"hsv kernel vs ref: crop {i} differs by {err[i] * px:.2f} px, "
            f"tolerance {tol[i] * px:.2f} px")
    top2 = np.sort(hr, axis=1)[:, -2:]
    bad = (lk != lr) & (top2[:, 1] - top2[:, 0] > 2 * tol)
    if bad.any():
        raise RuntimeError(f"hsv kernel vs ref: labels differ on crops "
                           f"{np.nonzero(bad)[0].tolist()}")
    return {"crops": len(crops), "max_diff_px": float(err.max() * px),
            "max_ambiguous_px": int(amb.max())}


def phase_uc1(service, *, frames: int = 300, crop: int = 224, seed: int = 7,
              batch_rows: int = 32, expect_backend: str = "pallas") -> dict:
    """UC1 through the service, checked against naive evaluation and the
    HSV reference. Returns the phase's numbers for printing."""
    video = SyntheticVideo(num_frames=frames, seed=seed)
    dogs = [o for o in video.objects if o.label == "dog"]
    crops = np.stack([crop_to_canonical(video.crop(o.frame_id, o.bbox), crop)
                      for o in dogs]).astype(np.float32)
    breed_gt = np.array([BREEDS.index(o.breed) for o in dogs])
    ids = np.arange(len(dogs), dtype=np.int64)
    chunks = [{"crop": crops[i:i + batch_rows],
               "breed_gt": breed_gt[i:i + batch_rows],
               "_row_id": ids[i:i + batch_rows]}
              for i in range(0, len(ids), batch_rows)]
    # whole noisy frames hit every HSV range; the solid dog crops do not
    noisy = np.stack([crop_to_canonical(video.frame(f), crop)
                      for f in range(min(frames, batch_rows))]
                     ).astype(np.float32)

    p_breed = udfs.planted_classifier(
        "DogBreedClassifier", BREEDS.index("great dane"),
        label_column="breed_gt", pixel_column="crop")
    p_color = udfs.color_predicate("black", size=crop, impl="pallas",
                                   name="DogColorClassifier")
    preds = [p_breed, p_color]
    q = Query(source=chunks, predicates=preds, batch_rows=batch_rows)

    events = []
    with launch.launch_hooks(events.append):
        report = run_query(service, preds, batches_of(q),
                           policy=EDDY_POLICIES["hydro"](), max_workers=4)
        naive = []
        for c in chunks:
            keep = np.ones(len(c["_row_id"]), bool)
            for p in preds:
                keep &= p.mask_from_outputs(p.evaluate_outputs(c))
            naive.append(c["_row_id"][keep])
        block_rows = block_divisor(crop, 64)
        hsv = [check_hsv_against_ref(x, block_rows) for x in
               [crops[i:i + batch_rows] for i in range(0, len(crops), batch_rows)]
               + [noisy]]
    naive = np.concatenate(naive)
    if not same_multiset(report.row_ids, naive):
        raise RuntimeError(
            f"uc1: service returned {len(report.row_ids)} rows, naive "
            f"evaluation {len(naive)}; the row-id multisets differ")
    check_backends(events, (), expect_backend)
    return {
        "rows_in": len(ids), "rows_out": len(report.row_ids),
        "launches": dict(collections.Counter(e.backend for e in events)),
        "hsv_vs_ref": {"crops": sum(h["crops"] for h in hsv),
                       "max_diff_px": max(h["max_diff_px"] for h in hsv),
                       "max_ambiguous_px": max(h["max_ambiguous_px"]
                                               for h in hsv)},
        "faults": report.faults,
    }


# --------------------------------------------------------------------------- #
# phase B: LLM review query                                                   #
# --------------------------------------------------------------------------- #
def phase_llm(service, cfg, *, reviews: int = 256, batch_rows: int = 16,
              seed: int = 0, expect_backend: str = "pallas") -> dict:
    """The review query through the service at ``cfg``'s widths, attention
    in the Pallas flash kernel.

    Tolerance. A bf16 model is defined only up to its rounding. The score
    of the XLA-attention path is compared with the same model in float32
    at full matmul precision on one batch; twice the largest gap,
    ``tol = 2 * max |s_xla - s_f32|``, is the tolerance. The flash kernel
    accumulates attention in float32, so it should be no further from the
    float32 model than the XLA path is, and then by the triangle inequality
    |s_pallas - s_xla| <= |s_pallas - s_f32| + |s_xla - s_f32| <= tol. A
    wrong kernel moves the hidden states by O(1) and the scores far beyond
    rounding. The service must return exactly the rows the direct
    evaluation passes, apart from rows with |score| <= tol."""
    cfg = dataclasses.replace(cfg, attention_impl="pallas")
    params = model_api(cfg).init_params(cfg, jax.random.key(seed))
    llm = build_llm_udf(cfg=cfg, params=params)
    pred = Predicate("LLM_is_food", llm, compare=lambda s: s > 0)
    data = make_reviews(reviews, seed=seed)

    def query():
        return Query(source=review_source(data), predicates=[pred],
                     trivial=[TrivialPredicate("rating", "<=", 1)],
                     batch_rows=batch_rows)

    events = []
    with launch.launch_hooks(events.append):
        report = run_query(service, [pred], batches_of(query()),
                           policy=EDDY_POLICIES["cost"](),
                           laminar_policy_factory=DataAware, max_workers=4)
        # direct evaluation over the same batches the service was given
        batches = list(batches_of(query()))
        scores = [np.asarray(llm(b.data), np.float32) for b in batches]

    tokens = batches[0].data["tokens"]
    sites = kernel_sites(llm_scorer(cfg, params), jnp.asarray(tokens))
    if not any(name == "flash_attention" for name, _ in sites):
        raise RuntimeError(f"no flash_attention kernel in the scorer: {sites}")
    check_backends(events, sites, expect_backend)

    s_xla = np.asarray(llm_scorer(
        dataclasses.replace(cfg, attention_impl="xla"), params)(tokens))
    f32_cfg = dataclasses.replace(cfg, attention_impl="xla", dtype="float32")
    f32_params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("float32"):
        s_f32 = np.asarray(llm_scorer(f32_cfg, f32_params)(tokens))
    tol = 2.0 * float(np.abs(s_xla - s_f32).max())
    pallas_vs_xla = float(np.abs(scores[0] - s_xla).max())
    if not pallas_vs_xla <= tol:
        raise RuntimeError(
            f"llm: pallas vs xla scores differ by {pallas_vs_xla:.6g}, "
            f"tolerance {tol:.6g}")

    all_ids = np.concatenate([b.row_ids for b in batches])
    all_scores = np.concatenate(scores)
    settled = np.abs(all_scores) > tol
    got = np.isin(all_ids, report.row_ids)
    if not same_multiset(report.row_ids, all_ids[got]):
        raise RuntimeError("llm: service returned rows it was never given")
    wrong = settled & (got != (all_scores > 0))
    if wrong.any():
        raise RuntimeError(
            f"llm: service and direct evaluation disagree on rows "
            f"{all_ids[wrong].tolist()}")
    return {
        "rows_in": len(all_ids), "rows_out": len(report.row_ids),
        "launches": dict(collections.Counter(e.backend for e in events)),
        "kernel_sites": {f"{n}/{b}": c for (n, b), c in sites.items()},
        "pallas_vs_xla": pallas_vs_xla, "tolerance": tol,
        "near_threshold_rows": int((~settled).sum()),
        "faults": report.faults,
    }


# --------------------------------------------------------------------------- #
class CompileSeconds:
    """Running total of XLA backend-compile seconds in this process."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += seconds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the model weights and the review data")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; "
              "nothing was run", file=sys.stderr)
        return 1
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    compiled = CompileSeconds()
    cfg = get_config("smollm-135m")
    phases = (
        ("uc1", lambda svc: phase_uc1(svc)),
        ("llm", lambda svc: phase_llm(svc, cfg, seed=args.seed)),
    )
    service = QueryService(max_concurrent=1)
    try:
        for name, phase in phases:
            c0, t0 = compiled.total, time.perf_counter()
            out = phase(service)
            wall = time.perf_counter() - t0
            print(f"[smoke] {name}: compile_s={compiled.total - c0!r} "
                  f"smoke_wall_s={wall!r} (smoke timings of one cold run, "
                  "not benchmark metrics)")
            print(f"[smoke] {name}: {json.dumps(out, sort_keys=True)}")
    finally:
        service.close(drain=False)
    dev = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
