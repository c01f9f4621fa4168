"""The HSV color kernel's launcher is built once per static shape: a warm
launch neither traces nor lowers, a new batch size builds one launcher,
every launch still reaches the timing hooks, and the histograms are those
of a launcher built fresh."""
import jax
import numpy as np
import pytest

from repro.kernels import hsv_color, launch
from repro.kernels.ref import COLOR_RANGES
from repro.udfs import color_predicate

SHAPES = [(4, 32, 32, 16), (3, 32, 32, 32), (8, 16, 16, 8)]
IDS = ["b4", "b3-one-block", "b8-small"]


def _crops(b, h, w, seed=0):
    return np.random.default_rng(seed).uniform(0, 255, (b, h, w, 3)).astype(np.float32)


def _hist(crops, block_rows):
    return np.asarray(hsv_color.hsv_color_hist(crops, COLOR_RANGES,
                                               block_rows=block_rows))


@pytest.mark.parametrize("b,h,w,block_rows", SHAPES, ids=IDS)
def test_warm_launch_neither_traces_nor_lowers(b, h, w, block_rows):
    crops = _crops(b, h, w)
    _hist(crops, block_rows)
    ctx = launch.LaunchContext("warm")
    with launch.launch_context(ctx):
        for _ in range(5):
            _hist(crops, block_rows)
    assert ctx.snapshot() == {"lower_s": 0.0, "compiles": 0, "cache_loads": 0}


@pytest.mark.parametrize("b,h,w,block_rows", SHAPES, ids=IDS)
def test_new_batch_size_builds_one_launcher(b, h, w, block_rows):
    hsv_color._launcher.cache_clear()
    _hist(_crops(b, h, w), block_rows)
    _hist(_crops(b, h, w, seed=1), block_rows)
    assert hsv_color._launcher.cache_info()[:2] == (1, 1)  # hits, misses
    for _ in range(2):
        _hist(_crops(b + 1, h, w), block_rows)
    assert hsv_color._launcher.cache_info()[:2] == (2, 2)


@pytest.mark.parametrize("b,h,w,block_rows", SHAPES, ids=IDS)
def test_every_launch_reaches_the_hooks(b, h, w, block_rows):
    events = []
    with launch.launch_hooks(events.append):
        for _ in range(3):
            _hist(_crops(b, h, w), block_rows)
    assert [(e.name, e.rows) for e in events] == [("hsv_color", b)] * 3


@pytest.mark.parametrize("b,h,w,block_rows", SHAPES, ids=IDS)
def test_histograms_equal_a_fresh_launcher(b, h, w, block_rows):
    crops = _crops(b, h, w, seed=2)
    _hist(crops, block_rows)  # the memoised launcher, warm
    cached = _hist(crops, block_rows)
    fresh = hsv_color._launcher.__wrapped__(
        b, h, w, len(COLOR_RANGES), block_rows, launch.default_interpret())
    planes = np.transpose(crops, (0, 3, 1, 2))
    want = np.asarray(fresh(planes, COLOR_RANGES.astype(np.float32)))[:, :, 0]
    assert cached.dtype == want.dtype and cached.tobytes() == want.tobytes()


def test_warm_color_udf_charges_no_lowering():
    """What ``lower_ms_per_batch.crops`` reads: a color UDF warmed over every
    bucket shape lowers nothing while its batches run."""
    size, batch = 32, 8
    udf = color_predicate(size=size).udf
    udf.ensure_ready()
    for rows in sorted({udf.launched_rows(n) for n in range(1, batch + 1)}):
        udf({"crop": _crops(rows, size, size)})
    ctx = launch.LaunchContext("q")
    with launch.launch_context(ctx):
        for seed, rows in enumerate((3, 8, 5, 1, 8)):
            jax.block_until_ready(udf({"crop": _crops(rows, size, size, seed)}))
    snap = ctx.snapshot()
    assert snap["lower_s"] == 0.0
    assert snap["cache_loads"] == 0 and snap["compiles"] == 0
