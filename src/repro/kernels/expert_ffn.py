"""Grouped SwiGLU expert FFN over expert-sorted rows (Pallas TPU).

The rows of ``x`` arrive grouped by expert, each group starting on a tile
boundary: expert ``e``'s rows lie at ``[offsets[e], offsets[e] +
sizes[e])`` with ``offsets`` the running sum of the group sizes rounded up
to ``block_rows`` (``padded_offsets``). So every row tile belongs to one
expert, and the grid walks row tiles: tile ``i`` computes
``down_e(silu(x @ gate_e) * (x @ up_e))`` for its expert ``e``, whose
weights its BlockSpecs fetch by the scalar-prefetched ``tile_expert``.

The grid has a static number of tiles, enough for every row being
assigned; ``pl.when`` skips the tiles past ``offsets[E]`` (the real rows),
and their index maps repeat the last real tile's blocks, so a skipped tile
fetches no weights and writes nothing back. Output rows past
``offsets[E]`` are left unwritten: the caller reads only the rows of its
groups. The rows of a group's last tile past its size are computed from
whatever ``x`` holds there (the caller passes zeros).

Each real tile reads its expert's whole gate, up and down matrices, so at
a few dozen rows per expert the kernel is bound by weight bandwidth. The
blocks hold whole expert matrices (DeepSeek-V2-Lite: 3 x 2048 x 1408 in
bfloat16, 16.5 MiB, twice for the pipeline), so the kernel asks for the
VMEM it needs beyond the default scoped limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch

DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def padded_offsets(group_sizes: jax.Array, block_rows: int) -> jax.Array:
    """(E,) group sizes -> (E + 1,) int32 group starts, each group rounded
    up to a whole number of ``block_rows`` tiles."""
    tiles = (group_sizes.astype(jnp.int32) + block_rows - 1) // block_rows
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(tiles * block_rows).astype(jnp.int32)])


def tile_experts(offsets: jax.Array, num_tiles: int, block_rows: int) -> jax.Array:
    """(num_tiles,) int32: the expert of each row tile. Tiles past the real
    rows take the last real tile's expert, so their weight blocks repeat."""
    e = offsets.shape[0] - 1
    last = jnp.maximum(offsets[e] // block_rows - 1, 0)
    starts = jnp.minimum(jnp.arange(num_tiles, dtype=jnp.int32), last) * block_rows
    return jnp.clip(jnp.searchsorted(offsets, starts, side="right") - 1,
                    0, e - 1).astype(jnp.int32)


def _expert_ffn_kernel(tile_expert_ref, offsets_ref, x_ref, wg_ref, wu_ref,
                       wd_ref, o_ref, *, block_rows: int, num_experts: int):
    del tile_expert_ref  # read by the index maps
    i = pl.program_id(0)

    @pl.when(i * block_rows < offsets_ref[num_experts])
    def _compute():
        x = x_ref[...]                                           # (Bm, D)
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)                 # (Bm, F)
        o_ref[...] = jnp.dot(h, wd_ref[0],
                             preferred_element_type=jnp.float32).astype(o_ref.dtype)


def expert_ffn_sorted(
    x: jax.Array,        # (R, D) rows grouped by expert, R % block_rows == 0
    w_gate: jax.Array,   # (E, D, F)
    w_up: jax.Array,     # (E, D, F)
    w_down: jax.Array,   # (E, F, D)
    offsets: jax.Array,  # (E + 1,) int32, ``padded_offsets``
    *,
    block_rows: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    r, d = x.shape
    e, _, f = w_gate.shape
    assert r % block_rows == 0, (r, block_rows)
    num_tiles = r // block_rows
    tiles = tile_experts(offsets, num_tiles, block_rows)

    def row_map(i, te, off):
        return (jnp.minimum(i, jnp.maximum(off[e] // block_rows - 1, 0)), 0)

    def weight_map(i, te, off):
        return (te[i], 0, 0)

    item = jnp.dtype(x.dtype).itemsize
    vmem = (2 * 3 * d * f * jnp.dtype(w_gate.dtype).itemsize     # weights, 2 buffers
            + 2 * 2 * block_rows * d * item                       # x and out, 2 buffers
            + 4 * block_rows * (3 * f + d))                       # f32 intermediates
    compiler_kwargs = ({"vmem_limit_bytes": int(vmem * 1.25) + 2 ** 22}
                       if vmem > DEFAULT_SCOPED_VMEM // 2 else None)

    kernel = functools.partial(_expert_ffn_kernel, block_rows=block_rows,
                               num_experts=e)
    return launch.pallas_call(
        kernel,
        name="expert_ffn",
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((block_rows, d), row_map),
            pl.BlockSpec((1, d, f), weight_map),
            pl.BlockSpec((1, d, f), weight_map),
            pl.BlockSpec((1, f, d), weight_map),
        ],
        out_specs=pl.BlockSpec((block_rows, d), row_map),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        dimension_semantics=("arbitrary",),
        compiler_kwargs=compiler_kwargs,
        interpret=interpret,
        rows=r,
        num_scalar_prefetch=2,
    )(tiles, offsets.astype(jnp.int32), x, w_gate, w_up, w_down)
