"""One run of one cell: set-up, a measured window, the check, one line.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``. The last line of standard output is the result; the
numbers the check compared, each beside its limit, are the last lines of
standard error and the last key of the result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import loadgen, spec

TRACE_ROOT = spec.REPO / ".chipbench" / "trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """XLA compiles in this process. JAX reports a program fetched from the
    persistent cache as a backend compile too; ``count`` leaves those out
    and ``loads`` counts them."""

    def __init__(self, jax):
        self.events = 0
        self.loads = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.events += 1
            self.seconds += seconds

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.loads += 1

    @property
    def count(self) -> int:
        return self.events - self.loads


def seed32(seed: int, salt: int) -> int:
    """A 31-bit seed for JAX keys, drawn from any whole ``seed``."""
    return int(np.random.SeedSequence([seed, salt]).generate_state(1)[0] >> 1)


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at a fixed path in the checkout; every program is written to it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(spec.REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Run:
    """What a metric reader sees of one run."""

    def __init__(self, *, records, setup_s, work, peaks, trace, gave_up=0.0):
        self.records: List[loadgen.Record] = records
        self.setup_s: float = setup_s
        self.work: Dict = work            # required work, see the deployment
        self.peaks: Optional[Dict] = peaks
        self.trace = trace                # trace.TraceSummary or None
        done = [r for r in records if r.done]
        self.done = done
        self.span_s = (max(r.report.finished_at for r in done)
                       - min(r.submitted for r in records)) if done else 0.0
        self.gave_up = gave_up            # monotonic: the last answer waited for

    def rate(self) -> Optional[float]:
        """Rows of every finished query over the time from the first submit
        to the last completion."""
        if not self.done or self.span_s <= 0:
            return None
        return sum(r.rows for r in self.done) / self.span_s

    def share(self, seconds: Optional[float]) -> Optional[float]:
        """``seconds`` as a percent of the traced window; None where the
        trace saw no device."""
        if (self.trace is None or not self.trace.chips or seconds is None
                or self.trace.window_s <= 0):
            return None
        return 100.0 * seconds / self.trace.window_s

    def roofline(self, kernel: str) -> Optional[float]:
        """Percent of the kernel's device time that its required work needs
        at the chip's peak (the larger of the compute and memory bounds)."""
        work = self.work.get("kernels", {}).get(kernel)
        if self.trace is None or not work or self.peaks is None:
            return None
        busy = self.trace.kernel_s(kernel)
        if busy <= 0:
            return None
        flops, nbytes = work
        need = max(flops / self.peaks["bf16_flops_per_s"],
                   nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * need / busy

    def latencies_ms(self) -> np.ndarray:
        """Each query's latency from when it was due to its answer. A query
        rejected, failed or unanswered counts with the longest wait the run
        gives it (to a minute past the window's close)."""
        return np.array([1e3 * ((r.report.finished_at if r.done else self.gave_up)
                                - r.due) for r in self.records])

    def mfu(self) -> Optional[float]:
        flops = self.work.get("flops")
        if self.trace is None or not flops or self.peaks is None:
            return None
        return self.share(flops / self.peaks["bf16_flops_per_s"])


def trace_dir() -> str:
    """This process's own directory for its trace, so that traced runs at
    once (rehearsals under several test workers) never share one."""
    return str(TRACE_ROOT / str(os.getpid()))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def main(argv=None, *, started: Optional[float] = None, small: bool = False,
         mix_override: Optional[dict] = None, control: bool = False) -> int:
    """Run one cell. ``small=True`` with ``mix_override`` (keys of the mix to
    replace) is the CPU rehearsal of ``tests/``: tiny widths and queries,
    any device, no peaks or persistent cache, Pallas in interpret mode.
    ``control=True`` puts the configuration's control, its reference in a
    lower precision, in the program's place (``chipbench/control.py``)."""
    started = time.monotonic() if started is None else started
    args = parse(argv)
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    config = spec.config(bench, cell["config"])
    mix = dict(spec.traffic(cell["traffic"]), **(mix_override or {}))
    module = spec.deployment_module(cell["config"])

    phases: Dict[str, float] = {"imports_s": time.monotonic() - started}
    t = time.monotonic()
    import jax

    devices = jax.devices()
    if not small and (jax.default_backend() != "tpu"
                      or len(devices) < cell["chips"]):
        log(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {jax.default_backend()} device(s); nothing was run")
        return 2
    cache = "off" if small else enable_compile_cache(jax)
    compiles = CompileCounter(jax)
    jax.block_until_ready(jax.numpy.zeros(1))
    kind = devices[0].device_kind
    peaks = None if small else spec.peaks(kind)
    phases["backend_init_s"] = time.monotonic() - t

    dep = module.Deployment(config, mix, args.seed, small=small)
    plan = loadgen.plan(mix, args.seconds)
    make_model = dep.make_model
    if control:
        make_model = lambda: (dep.make_model(), dep.use_control())
    for phase, step in (("weights_s", make_model),
                        ("data_s", lambda: dep.make_data(plan)),
                        ("warm_s", lambda: dep.warm(plan))):
        t = time.monotonic()
        step()
        phases[phase] = time.monotonic() - t
    setup_s = time.monotonic() - started
    setup_compiles, setup_events = compiles.count, compiles.events
    log("setup: " + " ".join(f"{k}={v!r}" for k, v in phases.items())
        + f" setup_s={setup_s!r} compiles={setup_compiles} "
        f"cache_loads={compiles.loads} compile_s={compiles.seconds!r} cache={cache}")

    # the set-up's objects live to the end: keep the collector off them
    gc.collect()
    gc.freeze()
    trace = None
    if args.trace:
        log_dir = trace_dir()
        shutil.rmtree(log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no Python call tracing
        options.host_tracer_level = 1       # the benchmark's spans, little else
        jax.profiler.start_trace(log_dir, profiler_options=options)
    dep.start_window()
    start = time.monotonic()
    with jax.profiler.TraceAnnotation("cb.window"):
        records = loadgen.drive(plan, dep.submit, args.seconds, start)
    gc.unfreeze()
    window_compiles = compiles.count - setup_compiles
    late = [r.submitted - r.due for r in records]
    log(f"window: compiles_in_window={window_compiles} backend_compile_events="
        f"{compiles.events - setup_events} queries={len(records)} "
        f"generator_late_ms_max={max(late, default=0.0) * 1e3!r}")
    if args.trace:
        jax.profiler.stop_trace()
        from chipbench import trace as tr

        events = tr.read_xplane(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        window = [(a, b) for kind_, name, a, b in events
                  if kind_ == "span" and name == "cb.window"]
        if not window:
            raise RuntimeError("the trace holds no window span")
        trace = tr.TraceSummary(events, window[0])

    log("udf: " + " ".join(f"{p.name}:calls={p.bench_recorder.calls},rows="
                           f"{p.bench_recorder.rows}" for p in dep.predicates))
    stats = devices[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    work = dep.required_work()
    dep.close()
    gc.collect()

    failed = sum(not r.done for r in records)
    if failed:
        log(f"failed queries: {sorted({r.error or r.report.state for r in records if not r.done})}")
    checks = dep.check(records)
    checks["query_faults"] = (float(sum(_faults(r) for r in records if r.done)), 0.0)
    # a number that could not be read (a row never scored) is a failure;
    # it prints as the largest float, so the line stays JSON
    checks = {k: (v if math.isfinite(v) else sys.float_info.max, lim)
              for k, (v, lim) in checks.items()}
    correct = all(v <= limit for v, limit in checks.values())

    run = Run(records=records, setup_s=setup_s, work=work, peaks=peaks,
              trace=trace, gave_up=start + args.seconds + loadgen.RESULT_WAIT_S)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(bench, group, args.workload):
        value = spec.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.device_ops(),
                               "idle_gaps": trace.idle_gaps()}
    result["check"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}={v!r} limit={lim!r} {'ok' if v <= lim else 'FAIL'}")
    print(json.dumps(result), flush=True)
    return 0


def _faults(r: loadgen.Record) -> int:
    f = r.report.faults
    return (len(f["quarantined"]) + len(f["degraded"]) + f["failures"]
            + f["retries"] + f["passthrough_batches"] + f["skipped_routes"])
