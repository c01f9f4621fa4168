"""Logical-axis sharding rules with divisibility-aware fallback.

Model code annotates every tensor dim with a *logical* name ("d_ff", "heads",
"batch", ...). ``spec_for`` resolves logical names to mesh axes through a
``Rules`` table, replicating any dim whose size does not divide the mapped
mesh axes (the GQA kv-head / grok-expert cases) — never a sharding error, by
construction.

Two standard rule sets:
  * TRAIN_RULES — FSDP x TP: weight d_model dims shard over "data"
    (ZeRO-3-style, GSPMD inserts all-gather/reduce-scatter), wide dims
    (d_ff / heads / vocab / experts) over "model"; batch over ("pod","data").
  * SERVE_RULES — TP only: weights shard over "model"; batch over
    ("pod","data"); decode KV caches shard seq over "model"
    (flash-decode partial-softmax combine, see models/attention.py).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisSpec = Union[None, str, Tuple[str, ...]]


class Rules:
    def __init__(self, table: Dict[str, AxisSpec], name: str = "rules"):
        self.table = dict(table)
        self.name = name

    def get(self, logical: Optional[str]) -> AxisSpec:
        if logical is None:
            return None
        return self.table.get(logical)

    def replace(self, **kw: AxisSpec) -> "Rules":
        t = dict(self.table)
        t.update(kw)
        return Rules(t, name=self.name + "+")

    def __repr__(self):
        return f"Rules({self.name})"


TRAIN_RULES = Rules(
    {
        "batch": ("pod", "data"),
        "seq": None,
        "seq_sp": "model",        # sequence-parallel inter-block activations
        "d_model": None,          # activation feature dim: replicated
        "d_model_w": "data",      # weight feature dim: FSDP over data
        "attn_dw": "data",        # attention in/out feature dim (== d_model_w at train)
        "d_sharded": None,        # transient constraint: replicated at train
        "experts_data": "data",   # ep2d storage (serve-only configs)
        "expert_dw": "data",      # expert weight feature dim (FSDP)
        "heads": "model",
        "kv_heads": "model",
        "d_ff": "model",
        "vocab": "model",
        "experts": "model",
        "state": None,
        "ssm_heads": "model",
        "ssm_inner": "model",
        "lru": "model",
        "lru_blocks": "model",
        "frames": None,
        "patches": None,
        "cache_seq": "model",
        "window": None,
        "conv": None,
        "layers": None,           # scan-stacked leading dim
    },
    name="train(FSDPxTP)",
)

SERVE_RULES = Rules(
    {
        "batch": ("pod", "data"),
        "seq": None,
        "seq_sp": "model",
        "d_model": None,
        "d_model_w": None,        # no FSDP at serve time: weights resident
        # attention projections of archs whose head count does NOT divide
        # the model axis (56, 12, 9 heads...) shard on the FEATURE dim at
        # serve: GBs of replicated projections become a tiny per-token psum
        # (SS Perf iteration, arctic decode args 14.8 -> ~3 GB/chip).
        "attn_dw": "model",
        "d_sharded": "model",     # transient activation constraint (out_proj)
        "experts_data": "data",   # ep2d resident-expert storage layout
        "expert_dw": "data",      # 480B experts can't be data-replicated
        "heads": "model",
        "kv_heads": "model",
        "d_ff": "model",
        "vocab": "model",
        "experts": "model",
        "state": None,
        "ssm_heads": "model",
        "ssm_inner": "model",
        "lru": "model",
        "lru_blocks": "model",
        "frames": None,
        "patches": None,
        "cache_seq": "model",     # sequence-sharded KV cache
        "window": None,
        "conv": None,
        "layers": None,
    },
    name="serve(TP)",
)


def axis_size(mesh: Mesh, axes: AxisSpec) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
    return n


def _present(mesh: Mesh, axes: AxisSpec) -> AxisSpec:
    """Drop mesh axes that do not exist on this mesh (e.g. 'pod' single-pod)."""
    if axes is None:
        return None
    if isinstance(axes, str):
        return axes if axes in mesh.axis_names else None
    kept = tuple(a for a in axes if a in mesh.axis_names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def parse_dims(logical: Union[str, Sequence[Optional[str]]]) -> Tuple[Optional[str], ...]:
    """Logical dims are space-separated strings so they stay pytree LEAVES.

    ``"layers d_model_w d_ff"`` -> ("layers", "d_model_w", "d_ff");
    ``"."`` marks a replicated dim: ``"batch . d_model"``.
    """
    if isinstance(logical, str):
        return tuple(None if t == "." else t for t in logical.split())
    return tuple(logical)


def spec_for(
    shape: Sequence[int],
    logical: Union[str, Sequence[Optional[str]]],
    rules: Rules,
    mesh: Mesh,
) -> P:
    """PartitionSpec for ``shape`` whose dims carry ``logical`` names.

    A dim is sharded over its mapped mesh axes only if its size is divisible
    by the product of those axis sizes AND no axis is claimed twice within
    the same spec; otherwise it is replicated.
    """
    logical = parse_dims(logical)
    assert len(shape) == len(logical), (shape, logical)
    out = []
    used: set = set()
    for size, name in zip(shape, logical):
        axes = _present(mesh, rules.get(name))
        if axes is None:
            out.append(None)
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        if any(a in used for a in tup):
            out.append(None)
            continue
        denom = math.prod(
            dict(zip(mesh.axis_names, mesh.devices.shape))[a] for a in tup
        )
        if denom > 1 and size % denom == 0:
            out.append(axes)
            used.update(tup)
        else:
            out.append(None)
    return P(*out)


def named_sharding(
    shape: Sequence[int],
    logical: Union[str, Sequence[Optional[str]]],
    rules: Rules,
    mesh: Mesh,
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, logical, rules, mesh))


def batch_axes(mesh: Mesh) -> AxisSpec:
    return _present(mesh, ("pod", "data"))


def constrain(x, logical: Union[str, Sequence[Optional[str]]], rules: Rules, mesh: Mesh):
    """with_sharding_constraint by logical dim names (no-op off-mesh)."""
    try:
        spec = spec_for(x.shape, logical, rules, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except ValueError:
        return x


def tree_named_shardings(shapes_tree, logical_tree, rules: Rules, mesh: Mesh):
    """Map matching (ShapeDtypeStruct tree, logical-dims-string tree) -> shardings."""
    return jax.tree.map(
        lambda sds, logical: named_sharding(sds.shape, logical, rules, mesh),
        shapes_tree,
        logical_tree,
    )


def tree_shape_dtypes(shapes_tree, logical_tree, rules: Rules, mesh: Mesh):
    """Attach shardings onto a ShapeDtypeStruct tree (for .lower())."""
    def _one(sds, logical):
        return jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=named_sharding(sds.shape, logical, rules, mesh)
        )

    return jax.tree.map(_one, shapes_tree, logical_tree)
