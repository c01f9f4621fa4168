"""Kernel-launch subsystem (Hydro §3.3), written for jax 0.9.

The paper's core observation is that UDF execution details must not leak
into the planner; GRACEFUL makes the same argument for UDF execution
internals sitting behind a uniform costed interface. Every Pallas kernel
launches through this module instead of hard-coding its own
pallas/interpret/XLA dispatch:

(a) **Compiler parameters** — ``compiler_params`` builds
    ``pltpu.CompilerParams``; ``VMEM``/``SMEM`` are re-exported so kernel
    files never touch ``pltpu`` directly.

(b) **Unified launch wrapper** — ``pallas_call`` is the single launch path
    for every kernel: compiled by Mosaic on a TPU backend, the Pallas
    interpreter elsewhere, with ONE ``interpret`` knob (None = auto).
    ``num_scalar_prefetch`` passes leading arguments as scalar-prefetch
    operands (SMEM values the index maps and body can read).
    ``resolve_impl`` centralizes the pallas/XLA-reference backend choice
    for the public ops wrappers (the XLA oracle is the dry-run path whose
    FLOPs XLA ``cost_analysis()`` can see).

(c) **Per-launch timing hooks** — registered hooks receive a
    ``LaunchEvent`` (kernel name, backend, rows, seconds) after each
    launch; ``connect_stats_board`` feeds them into
    ``StatsBoard.record_eval`` so kernel UDFs report cost-per-row like
    every other predicate (§3.3: statistics are collected DURING
    execution, never a-priori). With no hooks registered the wrapper adds
    no synchronization and no overhead.

    Hooks come in two scopes. GLOBAL hooks (``add_launch_hook(fn)``)
    observe every launch in the process — the right tool for tests and
    ad-hoc profiling. TOKEN hooks (``add_launch_hook(fn, token=...)``)
    are *thread-affine*: they fire only for launches made on threads that
    tagged themselves with the same token via ``set_launch_context`` /
    ``launch_context``. AQPExecutor registers its stats hook under its own
    token and tags every thread it owns, so two executors running
    CONCURRENTLY in one process each record only their own kernel
    launches (per-executor attribution — the old process-global bus
    cross-recorded).

(d) **Spans and compile counters** — an executor's token is a
    ``LaunchContext``: the query its threads serve, and what lowering and
    compiling on those threads cost it (one process-wide
    ``jax.monitoring`` listener charges each event to the context of the
    thread it happens on). ``span(name)`` opens a
    ``jax.profiler.TraceAnnotation`` tagged with that query id, so the
    program's spans land in the profiler's trace on the device's clock; with
    no trace being taken a span is one small native object.
"""
from __future__ import annotations

import functools
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.profiler import TraceAnnotation

__all__ = [
    "CompilerParams", "LaunchContext", "LaunchEvent", "SMEM", "VMEM",
    "add_launch_hook", "clear_launch_context", "compiler_params",
    "connect_stats_board", "current_launch_context", "default_interpret",
    "launch_context", "launch_hooks", "pallas_call", "remove_launch_hook",
    "resolve_impl", "roll", "set_launch_context", "span",
    "stats_board_hook",
]


# --------------------------------------------------------------------------- #
# (a) compiler parameters                                                     #
# --------------------------------------------------------------------------- #
CompilerParams = pltpu.CompilerParams

# Memory spaces and the lane/sublane rotate, re-exported so kernel files
# never touch pltpu directly. ``roll`` has ``jnp.roll`` semantics for a
# non-negative shift and lowers to the TPU's vector rotate.
VMEM = pltpu.VMEM
SMEM = pltpu.SMEM
roll = pltpu.roll


def compiler_params(dimension_semantics: Optional[Sequence[str]] = None, **kw):
    """``pltpu.CompilerParams`` with ``dimension_semantics`` as a tuple."""
    if dimension_semantics is not None:
        kw["dimension_semantics"] = tuple(dimension_semantics)
    return CompilerParams(**kw)


# --------------------------------------------------------------------------- #
# (b) unified launch path                                                     #
# --------------------------------------------------------------------------- #
def resolve_impl(impl: str) -> str:
    """'auto' -> 'pallas' on TPU, else 'xla' (the pure-jnp oracle path)."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def default_interpret() -> bool:
    """Interpret everywhere but on a real TPU backend."""
    return jax.default_backend() != "tpu"


def pallas_call(
    kernel: Callable,
    *,
    name: str,
    grid,
    in_specs,
    out_specs,
    out_shape,
    scratch_shapes=None,
    dimension_semantics: Optional[Sequence[str]] = None,
    compiler_kwargs: Optional[dict] = None,
    interpret: Optional[bool] = None,
    rows: Optional[int] = None,
    num_scalar_prefetch: int = 0,
):
    """The single kernel-launch path for every Pallas kernel in the repo.

    ``interpret=None`` auto-selects: compiled Pallas on TPU, the Pallas
    interpreter elsewhere (how kernels are validated on CPU CI). ``rows``
    is the row count reported to timing hooks (defaults to the leading dim
    of the first output). ``name`` also names the kernel in jaxprs and
    profiler traces. With ``num_scalar_prefetch=n`` the first ``n``
    call arguments are scalar-prefetch operands: the kernel and every
    index map receive their refs ahead of the blocked operands."""
    if interpret is None:
        interpret = default_interpret()
    launched = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_scalar_prefetch,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes or (),
        ),
        out_shape=out_shape,
        name=name,
        compiler_params=compiler_params(
            dimension_semantics=dimension_semantics, **(compiler_kwargs or {})
        ),
        interpret=interpret,
    )
    backend = "interpret" if interpret else "pallas"
    if rows is None:
        first = out_shape[0] if isinstance(out_shape, (list, tuple)) else out_shape
        rows = int(first.shape[0]) if first.shape else 1

    @functools.wraps(kernel)
    def call(*args):
        hooks = _snapshot_hooks()
        wd = _WATCHDOG
        if not hooks and wd is None:
            return launched(*args)
        # launch-deadline watchdog (core/faults.LaunchWatchdog): bracket
        # the eager launch so a scan thread can flag it if it hangs — the
        # launching thread is blocked inside XLA and cannot report for
        # itself. Tracer-phase calls are bracketed too (a hang during
        # trace/compile is just as wedging); only the TIMING event below
        # stays eager-only.
        token = wd.begin(name) if wd is not None else None
        t0 = time.perf_counter()
        try:
            out = launched(*args)
        finally:
            if wd is not None:
                wd.end(token)
        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(out)):
            # Under jit tracing no launch happened here — the elapsed time
            # is trace/compile time, and the compiled executable bypasses
            # this wrapper on later calls. Hooks observe eager launches
            # only; recording trace time would poison the cost EMA with
            # one sample orders of magnitude above steady state.
            return out
        if not hooks:
            return out
        jax.block_until_ready(out)
        event = LaunchEvent(
            name=name, backend=backend, rows=rows,
            seconds=time.perf_counter() - t0,
        )
        for hook in hooks:
            hook(event)
        return out

    return call


# --------------------------------------------------------------------------- #
# (c) per-launch timing hooks                                                 #
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class LaunchEvent:
    """One kernel launch: what ran, where, over how many rows, how long."""

    name: str
    backend: str  # "pallas" | "interpret"
    rows: int
    seconds: float


_HOOKS: List[Callable[[LaunchEvent], None]] = []
_TOKEN_HOOKS: dict = {}  # launch-context token -> [hooks]
_HOOKS_LOCK = threading.Lock()

# Process-global launch watchdog (core/faults.LaunchWatchdog or None).
# Kernel launches are process-wide resources, so unlike the timing hooks
# this seam is NOT token-scoped: any in-flight launch past its deadline is
# worth flagging regardless of which executor issued it.
_WATCHDOG = None


def set_launch_watchdog(wd):
    """Install the process-global launch watchdog; returns the previous
    one (restore it when done — tests use try/finally)."""
    global _WATCHDOG
    prev = _WATCHDOG
    _WATCHDOG = wd
    return prev


def current_launch_watchdog():
    return _WATCHDOG

# Thread-affine launch context: a worker/eddy thread tags itself with its
# executor's token; token-scoped hooks fire only for launches made on
# matching threads (per-executor attribution).
_TLS = threading.local()


def set_launch_context(token) -> None:
    """Tag the CURRENT thread's launches with ``token`` (None = untagged)."""
    _TLS.token = token


def clear_launch_context() -> None:
    _TLS.token = None


def current_launch_context():
    return getattr(_TLS, "token", None)


@contextmanager
def launch_context(token):
    """Scoped ``set_launch_context`` that restores the previous tag."""
    prev = current_launch_context()
    set_launch_context(token)
    try:
        yield
    finally:
        set_launch_context(prev)


def _snapshot_hooks() -> List[Callable[[LaunchEvent], None]]:
    if not _HOOKS and not _TOKEN_HOOKS:  # fast path: no lock, no overhead
        return []
    token = current_launch_context()
    with _HOOKS_LOCK:
        hooks = list(_HOOKS)
        if token is not None:
            hooks.extend(_TOKEN_HOOKS.get(token, ()))
        return hooks


def add_launch_hook(fn: Callable[[LaunchEvent], None], *, token=None):
    """Register a hook; with ``token``, only launches from threads tagged
    with the same launch context (``set_launch_context``) are observed."""
    with _HOOKS_LOCK:
        if token is None:
            _HOOKS.append(fn)
        else:
            _TOKEN_HOOKS.setdefault(token, []).append(fn)
    return fn


def remove_launch_hook(fn: Callable[[LaunchEvent], None]) -> None:
    with _HOOKS_LOCK:
        if fn in _HOOKS:
            _HOOKS.remove(fn)
        for token, hooks in list(_TOKEN_HOOKS.items()):
            if fn in hooks:
                hooks.remove(fn)
            if not hooks:
                del _TOKEN_HOOKS[token]


@contextmanager
def launch_hooks(*fns: Callable[[LaunchEvent], None]):
    for fn in fns:
        add_launch_hook(fn)
    try:
        yield
    finally:
        for fn in fns:
            remove_launch_hook(fn)


def stats_board_hook(board) -> Callable[[LaunchEvent], None]:
    """Hook feeding launches into ``StatsBoard.record_eval``.

    Kernels are compute UDFs, not filters, so rows_in == rows_out; what the
    board learns is the cost-per-row EMA the routing policies consume.
    Lazily-created kernel entries use the board's configured ``cost_alpha``
    so kernel cost estimates share the estimator horizon of every other
    predicate on the board. Entry creation goes through
    ``board.ensure_kernel``, which is thread-safe (launches report from
    predicate worker threads while the eddy thread reads the same board)
    and namespaces the entry ``kernel:<name>`` if a declared routing
    predicate already owns the kernel's launch name."""

    def hook(event: LaunchEvent) -> None:
        board.ensure_kernel(event.name).record_eval(
            event.rows, event.rows, event.seconds
        )

    return hook


def connect_stats_board(board, *, token=None) -> Callable[[LaunchEvent], None]:
    """Register (and return, for later removal) a stats-board hook.

    With ``token``, the hook is thread-affine: only launches from threads
    tagged with that launch context reach ``board`` — how concurrent
    executors keep per-executor attribution."""
    return add_launch_hook(stats_board_hook(board), token=token)


# --------------------------------------------------------------------------- #
# (d) spans and compile counters                                              #
# --------------------------------------------------------------------------- #
_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
# JAX reports a program fetched from the persistent cache as a backend
# compile too; ``LaunchContext.snapshot`` subtracts the cache loads
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_LISTENING = False


class LaunchContext:
    """An executor's launch token: the query its threads serve, and what
    lowering and compiling on those threads cost it. ``lower_s`` sums the
    seconds spent tracing jaxprs and lowering them to MLIR; ``compiles``
    counts backend compiles that were not persistent-cache loads;
    ``cache_loads`` counts those loads."""

    def __init__(self, query: Optional[str] = None):
        self.query = query
        self._lock = threading.Lock()
        self._lower_s = 0.0
        self._backend_compiles = 0
        self._cache_loads = 0
        _listen_for_compiles()

    def _note(self, event: str, seconds: float = 0.0) -> None:
        with self._lock:
            if event in _LOWER_EVENTS:
                self._lower_s += seconds
            elif event == _BACKEND_COMPILE_EVENT:
                self._backend_compiles += 1
            elif event == _CACHE_HIT_EVENT:
                self._cache_loads += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"lower_s": self._lower_s,
                    "compiles": self._backend_compiles - self._cache_loads,
                    "cache_loads": self._cache_loads}


def _charge(event: str, seconds: float = 0.0, **_) -> None:
    """A ``jax.monitoring`` event, charged to this thread's context."""
    ctx = current_launch_context()
    if isinstance(ctx, LaunchContext):
        ctx._note(event, seconds)


def _listen_for_compiles() -> None:
    """Register the process-wide compile listener, once."""
    global _LISTENING
    with _HOOKS_LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    jax.monitoring.register_event_duration_secs_listener(_charge)
    jax.monitoring.register_event_listener(_charge)


def span(name: str, qid: Optional[str] = None) -> TraceAnnotation:
    """A profiler span named ``name`` (``hydro.<layer>``), tagged with
    ``qid`` or else the query of this thread's launch context."""
    if qid is None:
        qid = getattr(current_launch_context(), "query", None)
    return TraceAnnotation(name) if qid is None else TraceAnnotation(name, qid=qid)
