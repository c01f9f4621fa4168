"""Unit tests for the kernel-launch subsystem (repro.kernels.launch):
compiler parameters, launch timing hooks feeding StatsBoard, and the
no-direct-pallas_call invariant over kernel files.
"""
import os

import jax
import jax.numpy as jnp

from repro.kernels import launch
from repro.kernels import ops
from repro.core.stats import StatsBoard


# --------------------------- compiler parameters --------------------------- #
def test_compiler_params_builds_dimension_semantics():
    params = launch.compiler_params(dimension_semantics=["parallel", "arbitrary"])
    assert isinstance(params, launch.CompilerParams)
    assert params.dimension_semantics == ("parallel", "arbitrary")


# ------------------------------ launch path ------------------------------- #
def test_resolve_impl_auto_matches_backend():
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert launch.resolve_impl("auto") == expect
    assert launch.resolve_impl("pallas") == "pallas"
    assert launch.resolve_impl("xla") == "xla"


def test_launch_hooks_fire_per_launch(rng):
    events = []
    logits = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    with launch.launch_hooks(events.append):
        ops.moe_topk_router(logits, 2, impl="pallas")
    assert len(events) == 1
    ev = events[0]
    assert ev.name == "moe_router"
    assert ev.rows == 32
    assert ev.seconds > 0
    assert ev.backend in ("pallas", "interpret")
    # hook removed on exit: no further events
    ops.moe_topk_router(logits, 2, impl="pallas")
    assert len(events) == 1


def test_stats_board_hook_feeds_record_eval(rng):
    """Kernel launches report cost-per-row like every other predicate (§3.3)."""
    board = StatsBoard([])
    hook = launch.connect_stats_board(board)
    try:
        crops = jnp.asarray(rng.uniform(0, 255, (4, 32, 16, 3)), jnp.float32)
        ops.hsv_color_classify(crops, impl="pallas", block_rows=16)
    finally:
        launch.remove_launch_hook(hook)
    st = board["hsv_color"]
    assert st.measured
    assert st.batches == 1
    assert st.tickets == 4            # rows_in == batch size
    assert st.wins == 0               # compute UDF: no rows dropped
    assert st.cost() > 0              # cost-per-row EMA got a sample


def test_launch_hooks_ignore_jit_tracing(rng):
    """Under jit, no launch happens in the wrapper: recording trace/compile
    time would poison the cost EMA with one inflated sample."""
    events = []
    logits = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    jitted = jax.jit(lambda lg: ops.moe_topk_router(lg, 2, impl="pallas"))
    with launch.launch_hooks(events.append):
        jitted(logits)          # traces + compiles + runs
        jitted(logits)          # cached executable, bypasses the wrapper
    assert events == []


def test_stats_board_hook_inherits_cost_alpha(rng):
    board = StatsBoard([], cost_alpha=0.05)
    hook = launch.connect_stats_board(board)
    try:
        logits = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
        ops.moe_topk_router(logits, 2, impl="pallas")
    finally:
        launch.remove_launch_hook(hook)
    assert board["moe_router"].cost_per_row.alpha == 0.05


def test_no_direct_pallas_launches_in_kernel_files():
    """All kernel launches must go through repro.kernels.launch."""
    kdir = os.path.dirname(ops.__file__)
    offenders = []
    for fname in sorted(os.listdir(kdir)):
        if not fname.endswith(".py") or fname == "launch.py":
            continue
        src = open(os.path.join(kdir, fname)).read()
        if "pl.pallas_call" in src or "CompilerParams" in src:
            offenders.append(fname)
    assert not offenders, offenders
