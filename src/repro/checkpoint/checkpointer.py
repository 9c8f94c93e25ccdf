"""Sharded, atomic, elastic checkpointing.

Layout: one .npy per leaf (logical FULL arrays via from_storage, so restores
are topology-independent — save on a 256-chip mesh, restore on 512: "elastic
scaling") + a JSON manifest with step/config. Writes go to a temp dir that is
atomically renamed; an optional background thread makes saves async. The
trainer's restart path (ft/) relies on `latest_step` + bit-exact restore
(tested in tests/test_integration.py).

At datacenter scale each host would write only its local shards; the
manifest format already records per-leaf paths so that change is local to
_save_tree/_load_tree.
"""

from __future__ import annotations

import json
import os
import shutil
import threading

import jax
import numpy as np

from repro.core.dist import DistConfig
from repro.models import runtime as RT


def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], f"{prefix}{k}/"))
    elif isinstance(tree, (tuple, list)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}/"))
    else:
        out[prefix[:-1]] = tree
    return out


class Checkpointer:
    def __init__(self, root: str, async_save: bool = False):
        self.root = root
        self.async_save = async_save
        self._thread: threading.Thread | None = None
        os.makedirs(root, exist_ok=True)

    # ------------------------------------------------------------- save --
    def save(self, step: int, storage, opt_state, model, dcfg: DistConfig,
             extra: dict | None = None):
        metas = model.metas(dcfg)

        def logical(tree):
            # one group at a time to host memory: the device never holds
            # more than one group's full copy next to the training state
            return {k: jax.tree.map(np.asarray, RT.tree_from_storage(
                tree[k], metas[k], dcfg)) for k in tree}

        if self._thread is not None:
            self._thread.join()     # previous async save must land first
        host = _flatten({"params": logical(storage),
                         "m": logical(opt_state["m"]),
                         "v": logical(opt_state["v"])})
        host["opt_step"] = np.asarray(opt_state["step"])

        def _write():
            tmp = os.path.join(self.root, f".tmp_step_{step}")
            final = os.path.join(self.root, f"step_{step:08d}")
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
            index = {}
            for k, v in host.items():
                fn = k.replace("/", "__") + ".npy"
                np.save(os.path.join(tmp, fn), v)
                index[k] = fn
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump({"step": step, "leaves": index,
                           "extra": extra or {}}, f)
            shutil.rmtree(final, ignore_errors=True)
            os.rename(tmp, final)   # atomic publish

        if self.async_save:
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()
        else:
            _write()
        return step

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    # ---------------------------------------------------------- restore --
    def latest_step(self) -> int | None:
        steps = [int(d.split("_")[1]) for d in os.listdir(self.root)
                 if d.startswith("step_")]
        return max(steps) if steps else None

    def restore(self, step: int, model, dcfg: DistConfig):
        """Returns (storage, opt_state) re-sharded for `dcfg` — restoring on
        a different mesh than the save is supported (elastic)."""
        d = os.path.join(self.root, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        leaves = {k: np.load(os.path.join(d, fn))
                  for k, fn in manifest["leaves"].items()}
        metas = model.metas(dcfg)

        def unflatten(prefix, template):
            if isinstance(template, dict):
                return {k: unflatten(f"{prefix}{k}/", template[k])
                        for k in sorted(template)}
            return leaves[prefix[:-1]]

        abstract = RT.model_abstract_storage(model, dcfg)
        logical = unflatten("params/", abstract)
        storage = {k: RT.tree_to_storage(logical[k], metas[k], dcfg)
                   for k in logical}
        m = unflatten("m/", abstract)
        v = unflatten("v/", abstract)
        opt_state = {
            "m": {k: RT.tree_to_storage(m[k], metas[k], dcfg) for k in m},
            "v": {k: RT.tree_to_storage(v[k], metas[k], dcfg) for k in v},
            "step": jax.numpy.asarray(leaves["opt_step"]),
        }
        return storage, opt_state, manifest
