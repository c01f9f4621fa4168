"""BENCHMARK.json, and the harness finding each piece by name."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

from chipbench import spec  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_piece_is_found_by_name():
    for cell in BENCH["workloads"]:
        cfg = spec.config(BENCH, cell["config"])
        assert cfg["sizes"]
        assert hasattr(spec.deployment_module(cell["config"]), "Deployment")
        mix = spec.traffic(cell["traffic"])
        assert callable(spec.loop(mix["loop"]).plan)
        (law, _), = mix["query_rows"].items()
        assert callable(spec.size_law(law).sizes)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        spec.workload(BENCH, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.traffic("no_such_mix")
    with pytest.raises(FileNotFoundError):
        spec.loop("no_such_loop")
    with pytest.raises(FileNotFoundError):
        spec.size_law("no_such_law")


def test_peaks_by_device_kind():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_benchmark_json_shape():
    cells = {c["name"] for c in BENCH["workloads"]}
    assert 1 <= BENCH["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for c in m["workloads"]:
            assert c in cells
            assert c in e2e[m["moves"]].get("workloads", cells)
    for c in cells:
        reported = [n for n, m in e2e.items() if c in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(c in m["workloads"] for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_run_exits_nonzero_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(REPO / "chipbench/run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "2147483699", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
