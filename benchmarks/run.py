"""Benchmark aggregator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/harness.py), plus
a dry-run/roofline summary from results/dryrun/ when present.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only uc1
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import (  # noqa: E402
    bench_chaos,
    bench_coalescing,
    bench_content_routing,
    bench_kernels,
    bench_routing_throughput,
    bench_serve,
    bench_uc1_routing,
    bench_uc1_synthetic,
    bench_uc2_reuse,
    bench_uc3_laminar,
    bench_uc4_databalance,
)
from benchmarks.harness import csv_header, record  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SUITES = {
    "uc1": bench_uc1_routing.main,          # Fig 5 + Table 1 / Fig 6
    "uc1_synth": bench_uc1_synthetic.main,  # Fig 7
    "uc2": bench_uc2_reuse.main,            # Fig 8 / Fig 9 + repeated trace
    "uc2_repeat": bench_uc2_reuse.main_repeat,  # cross-query reuse smoke
    "uc3": bench_uc3_laminar.main,          # Fig 11 / Fig 12
    "uc4": bench_uc4_databalance.main,      # Fig 14
    "content": bench_content_routing.main,  # beyond-paper (§2.2 lineage)
    "kernels": bench_kernels.main,          # kernel hot spots
    "routing": bench_routing_throughput.main,  # sharded eddy core scaling
    "coalescing": bench_coalescing.main,    # adaptive micro-batch fusing
    "chaos": bench_chaos.main,              # fault injection + retry gates
    "serve": bench_serve.main,              # multi-tenant QueryService goodput
}


def dryrun_summary() -> None:
    """Roofline rows from the dry-run artifacts (EXPERIMENTS.md source)."""
    pat = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun", "*.json")
    files = sorted(glob.glob(pat))
    if not files:
        record("dryrun/none", 0.0, "run launch/dryrun.py first")
        return
    ok = err = skip = 0
    for f in files:
        r = json.load(open(f))
        s = r.get("status")
        ok += s == "ok"
        err += s == "error"
        skip += s == "skipped"
        if "roofline" in r:
            t = r["roofline"]["terms"]
            record(
                f"roofline/{r['arch']}/{r['shape']}",
                t["compute_s"] * 1e6,
                f"dominant={t['dominant']};fraction={t['roofline_fraction']:.3f};"
                f"mem_s={t['memory_s']:.3g};coll_s={t['collective_s']:.3g}",
            )
    record("dryrun/summary", 0.0, f"ok={ok};skipped={skip};errors={err}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(SUITES) + ["dryrun"])
    args = ap.parse_args()
    enable_compile_cache()

    csv_header()
    failures = []
    suites = SUITES if args.only in (None, "dryrun") else {args.only: SUITES[args.only]}
    if args.only == "dryrun":
        suites = {}
    for name, fn in suites.items():
        try:
            fn()
        except Exception as e:
            failures.append(name)
            record(f"{name}/FAILED", 0.0, f"{type(e).__name__}:{e}")
            traceback.print_exc()
    if args.only in (None, "dryrun"):
        dryrun_summary()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
