"""CPU rehearsal of ``chip_smoke.py``: both phases end to end through a
``QueryService`` at smoke widths with the kernels in interpret mode and the
same correctness checks, the refusal to run without a TPU, and where the
entry points put the compilation cache."""
import dataclasses
import importlib.util
import types
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch.serve import QueryReport, QueryService

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def service():
    svc = QueryService(max_concurrent=1)
    try:
        yield svc
    finally:
        svc.close(drain=False)


def test_rehearse_uc1_phase(smoke, service):
    out = smoke.phase_uc1(service, frames=40, crop=32, batch_rows=8,
                          expect_backend="interpret")
    assert out["rows_in"] > 0
    assert 0 < out["rows_out"] < out["rows_in"]
    assert set(out["launches"]) == {"interpret"}
    assert out["hsv_vs_ref"]["crops"] > out["rows_in"]  # dog crops + frames


def test_rehearse_llm_phase(smoke, service):
    # smoke widths; bf16 as on the chip, so the float32 gap that sets the
    # tolerance is a real rounding gap
    cfg = dataclasses.replace(get_config("smollm-135m").reduce_for_smoke(),
                              dtype="bfloat16")
    out = smoke.phase_llm(service, cfg, reviews=60, batch_rows=8,
                          expect_backend="interpret")
    assert out["rows_in"] > 0
    assert out["kernel_sites"] == {"flash_attention/interpret": 1}
    assert out["launches"] == {}  # the kernel runs inside the jitted model
    assert 0 < out["tolerance"]


def test_backend_check_rejects_interpret(smoke):
    with pytest.raises(RuntimeError, match="expected only 'pallas'"):
        smoke.check_backends((), {("flash_attention", "interpret"): 1},
                             "pallas")


CLEAN_FAULTS = {"failures": 0, "retries": 0, "passthrough_batches": 0,
                "skipped_routes": 0, "quarantined": [], "degraded": [],
                "unquarantined": []}


@pytest.mark.parametrize("dirty", [
    {"state": "FAILED"}, {"failures": 1}, {"retries": 2},
    {"passthrough_batches": 1}, {"skipped_routes": 1},
    {"quarantined": ["p"]}, {"degraded": ["p"]},
], ids=lambda d: next(iter(d)))
def test_run_query_refuses_unclean_result(smoke, dirty):
    # a pass-through verdict keeps every row, so a query can end DONE with
    # rows and still not be an answer the smoke may accept
    def service_reporting(state, faults):
        report = QueryReport(qid="q0", state=state, priority=0.0,
                             deadline_s=None, submitted_at=0.0,
                             faults={**CLEAN_FAULTS, **faults})
        handle = types.SimpleNamespace(result=lambda timeout: report)
        return types.SimpleNamespace(submit=lambda *a, **k: handle), report

    state = dirty.pop("state", "DONE")
    service, _ = service_reporting(state, dirty)
    with pytest.raises(RuntimeError, match="q0"):
        smoke.run_query(service, [], iter(()))
    service, clean = service_reporting("DONE", {})
    assert smoke.run_query(service, [], iter(())) is clean


def test_main_refuses_cpu_backend(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache() == str(
        ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
