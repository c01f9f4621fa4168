"""Fault-tolerant checkpointing: async, atomic, elastic-reshardable.

Layout: ``<dir>/step_<N>/`` containing ``manifest.json`` (tree structure,
shapes, dtypes) + ``arrays.npz``. Writes go to ``step_<N>.tmp`` and are
renamed only when complete — a crash mid-save can never corrupt the latest
checkpoint (restart discovery simply ignores ``*.tmp``). Saves run on a
background thread (training continues); ``wait()`` joins before the next
save or shutdown.

Elastic restore: arrays are loaded host-side and ``jax.device_put`` with the
*target* sharding — a checkpoint written on one mesh restores onto any other
mesh (different device count / topology), which is the restart path after a
failed pod is replaced or the job is rescaled.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

import jax
import numpy as np


def _flatten(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith("step_") and not name.endswith(".tmp"):
            try:
                steps.append(int(name.split("_", 1)[1]))
            except ValueError:
                continue
    return max(steps) if steps else None


class Checkpointer:
    def __init__(self, directory: str, *, keep: int = 3, async_save: bool = True):
        self.directory = directory
        self.keep = keep
        self.async_save = async_save
        os.makedirs(directory, exist_ok=True)
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._pending: Optional[Future] = None
        self._lock = threading.Lock()

    # ------------------------------ save ------------------------------ #
    def save(self, step: int, tree: Any) -> None:
        # snapshot to host memory synchronously (cheap), write async
        leaves, treedef = _flatten(tree)
        host = [np.asarray(x) for x in leaves]
        manifest = {
            "step": step,
            "treedef": jax.tree_util.tree_structure(tree).serialize_using_proto().hex(),
            "num_leaves": len(host),
            "dtypes": [str(a.dtype) for a in host],
            "shapes": [list(a.shape) for a in host],
        }
        if self.async_save:
            self.wait()
            self._pending = self._pool.submit(self._write, step, host, manifest)
        else:
            self._write(step, host, manifest)

    def _write(self, step: int, host, manifest) -> None:
        final = os.path.join(self.directory, f"step_{step}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        np.savez(os.path.join(tmp, "arrays.npz"), *host)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)  # atomic publish
        self._gc()

    def _gc(self) -> None:
        with self._lock:
            steps = sorted(
                int(n.split("_", 1)[1])
                for n in os.listdir(self.directory)
                if n.startswith("step_") and not n.endswith(".tmp")
            )
            for s in steps[: -self.keep] if self.keep else []:
                shutil.rmtree(os.path.join(self.directory, f"step_{s}"), ignore_errors=True)

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.result()
            self._pending = None

    def close(self) -> None:
        """Drain pending saves and join the writer thread (non-daemon —
        leaving it alive trips the test session's leaked-thread guard)."""
        self.wait()
        self._pool.shutdown(wait=True)

    # ----------------------------- restore ---------------------------- #
    def restore(self, step: int, target: Any = None) -> Any:
        """Restore step. ``target``: pytree of arrays or ShapeDtypeStructs
        (possibly with .sharding) — enables elastic re-mesh on load."""
        path = os.path.join(self.directory, f"step_{step}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        data = np.load(os.path.join(path, "arrays.npz"))
        host = [data[f"arr_{i}"] for i in range(manifest["num_leaves"])]
        treedef = _deserialize_treedef(manifest["treedef"])
        tree = jax.tree_util.tree_unflatten(treedef, host)
        if target is not None:
            def place(t, a):
                sh = getattr(t, "sharding", None)
                a = np.asarray(a).astype(t.dtype) if hasattr(t, "dtype") else np.asarray(a)
                if sh is not None:
                    return jax.device_put(a, sh)
                return jax.device_put(a)

            tree = jax.tree.map(place, target, tree)
        return tree

    def restore_latest(self, target: Any = None):
        step = latest_step(self.directory)
        if step is None:
            return None, None
        return step, self.restore(step, target)


def _deserialize_treedef(proto_hex: str):
    from jax.tree_util import PyTreeDef, default_registry

    return PyTreeDef.deserialize_using_proto(default_registry, bytes.fromhex(proto_hex))
