"""Entry point of the chip benchmark; see ``chipbench/harness.py``.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, off a TPU or with fewer chips than the
cell asks for.
"""
import sys
import time

STARTED = time.monotonic()

if __name__ == "__main__":
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(repo / "src"), str(repo)]
    from chipbench import harness

    sys.exit(harness.main(sys.argv[1:], started=STARTED))
