"""Find a cell's pieces by name: ``BENCHMARK.json`` and the files under
``chipbench/``. Adding a configuration, a mix or a metric adds files; no
file here names one."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def load_benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    """The configuration's entry, with its file's contents under ``sizes``."""
    entry = dict(_entry(bench["configs"], name, "configuration"))
    with open(REPO / entry["file"]) as f:
        entry["sizes"] = json.load(f)
    return entry


def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deployment_module(config_name: str) -> ModuleType:
    """``configs/<config>.py``: how the configuration is deployed."""
    return _module(HERE / "configs" / f"{config_name}.py",
                   f"chipbench_config_{config_name}")


def traffic(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def loop(name: str) -> ModuleType:
    """``loops/<loop>.py``: an arrival law, its ``plan`` and ``drive``."""
    return _module(HERE / "loops" / f"{name}.py", f"chipbench_loop_{name}")


def size_law(name: str) -> ModuleType:
    """``sizes/<law>.py``: a law of query sizes, its ``sizes``."""
    return _module(HERE / "sizes" / f"{name}.py", f"chipbench_sizes_{name}")


def metric_reader(name: str) -> ModuleType:
    """``metrics/<metric>.py``, whose ``read(run)`` returns the value or
    None when the run has nothing to read."""
    return _module(HERE / "metrics" / f"{name}.py", f"chipbench_metric_{name}")


def metrics_of(bench: dict, group: str, workload_name: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that a cell reports."""
    return [m for m in bench[group]
            if workload_name in m.get("workloads", [workload_name])]


def peaks(device_kind: str) -> Dict[str, float]:
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
