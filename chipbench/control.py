"""The control of a cell's check: its reference, in the next precision
below the configuration's, in the program's place. Its readings set the
upper end of each limit (see PERF.md); the benchmark's own runs never run
it. Several seeds run in one process:

  python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 1,2,3
"""
import argparse
import sys
from pathlib import Path

if __name__ == "__main__":
    repo = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(repo / "src"), str(repo)]
    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", default="10")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    for seed in args.seeds.split(","):
        rc = harness.main(["--workload", args.workload, "--seed", seed,
                           "--seconds", args.seconds], control=True)
        if rc:
            sys.exit(rc)
