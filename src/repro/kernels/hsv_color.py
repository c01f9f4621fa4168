"""HSV color classification (Pallas TPU) — the paper's DogColorClassifier.

The paper classifies object colors by checking pixel values against HSV
ranges (e.g. red = (0,50,70)..(9,255,255)). This kernel fuses RGB->HSV
conversion, range bucketing (first match wins, remainder = 'other') and the
per-image histogram reduction. Grid (B, num_row_blocks): row blocks innermost
accumulate per-color column counts in VMEM scratch; pixels stream HBM->VMEM
once.

Layout: the wrapper hands the kernel channel PLANES (B, 3, H, W), so every
vector op works on (rows, W) tiles with W on the lanes; the (C, 6) color
ranges sit in SMEM and are read as scalars.

The launcher is built once per static shape (``_launcher``) and reused:
each build makes Pallas return a fresh ``jax.jit`` function, whose cache
belongs to that function object, so a launcher built anew on every call
traces the body, lowers it to MLIR and fetches the executable again each
time. The eager wrapper from ``launch.pallas_call`` still runs on every
call, so the timing hooks and the launch watchdog see each launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch


def _hsv_kernel(
    rgb_ref,    # (1, 3, Br, W) channel planes
    rng_ref,    # (C, 6) SMEM
    hist_ref,   # (1, C+1, 1) output
    acc_ref,    # scratch (C+1, W) f32 per-column counts
    *, num_row_blocks: int, n_colors: int, total_px: int,
):
    ri = pl.program_id(1)

    @pl.when(ri == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = rgb_ref[0, 0].astype(jnp.float32)   # (Br, W)
    g = rgb_ref[0, 1].astype(jnp.float32)
    b = rgb_ref[0, 2].astype(jnp.float32)
    mx = jnp.maximum(jnp.maximum(r, g), b)
    mn = jnp.minimum(jnp.minimum(r, g), b)
    diff = mx - mn
    safe = jnp.where(diff == 0, 1.0, diff)
    h = jnp.where(
        mx == r,
        (g - b) / safe % 6.0,
        jnp.where(mx == g, (b - r) / safe + 2.0, (r - g) / safe + 4.0),
    )
    h = jnp.where(diff == 0, 0.0, h) * 30.0
    s = jnp.where(mx == 0, 0.0, diff / jnp.where(mx == 0, 1.0, mx)) * 255.0
    v = mx

    taken = jnp.zeros(h.shape, jnp.bool_)
    for c in range(n_colors):  # first matching range wins, in order
        inrange = (
            (h >= rng_ref[c, 0]) & (h <= rng_ref[c, 3])
            & (s >= rng_ref[c, 1]) & (s <= rng_ref[c, 4])
            & (v >= rng_ref[c, 2]) & (v <= rng_ref[c, 5])
        )
        hit = inrange & ~taken
        taken = taken | inrange
        acc_ref[c:c + 1, :] += jnp.sum(hit.astype(jnp.float32), axis=0,
                                       keepdims=True)
    acc_ref[n_colors:n_colors + 1, :] += jnp.sum(
        (~taken).astype(jnp.float32), axis=0, keepdims=True)

    @pl.when(ri == num_row_blocks - 1)
    def _final():
        hist_ref[0] = (
            jnp.sum(acc_ref[...], axis=1, keepdims=True) / total_px
        ).astype(hist_ref.dtype)


@functools.lru_cache(maxsize=16)
def _launcher(b: int, hh: int, ww: int, c: int, block_rows: int,
              interpret: bool):
    """The launch wrapper for one static shape, built on its first call."""
    nr = hh // block_rows
    kernel = functools.partial(
        _hsv_kernel, num_row_blocks=nr, n_colors=c, total_px=hh * ww
    )
    return launch.pallas_call(
        kernel,
        name="hsv_color",
        grid=(b, nr),
        in_specs=[
            pl.BlockSpec((1, 3, block_rows, ww), lambda bi, ri: (bi, 0, ri, 0)),
            pl.BlockSpec((c, 6), lambda bi, ri: (0, 0),
                         memory_space=launch.SMEM),
        ],
        out_specs=pl.BlockSpec((1, c + 1, 1), lambda bi, ri: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c + 1, 1), jnp.float32),
        scratch_shapes=[launch.VMEM((c + 1, ww), jnp.float32)],
        dimension_semantics=("parallel", "arbitrary"),
        interpret=interpret,
        rows=b,
    )


def hsv_color_hist(
    crops: jax.Array,   # (B, H, W, 3) RGB in [0, 255]
    ranges: jax.Array,  # (C, 6) lo/hi HSV
    *,
    block_rows: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    b, hh, ww, _ = crops.shape
    c = ranges.shape[0]
    block_rows = min(block_rows, hh)
    assert hh % block_rows == 0, (hh, block_rows)
    if interpret is None:
        interpret = launch.default_interpret()

    planes = jnp.transpose(crops.astype(jnp.float32), (0, 3, 1, 2))
    hist = _launcher(b, hh, ww, c, block_rows, interpret)(
        planes, ranges.astype(jnp.float32))
    return hist[:, :, 0]
