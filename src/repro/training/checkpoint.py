"""Minimal npz checkpointing: flatten a params pytree to path-keyed arrays.

Paths encode list indices and dict keys ("blocks.0.mixer.wq"); restoring
rebuilds into an existing template pytree (shape-checked)."""
from __future__ import annotations

import os
from typing import Any, Dict

import jax.numpy as jnp
import numpy as np


def _flatten(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}."))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}."))
    elif tree is None:
        pass
    else:
        out[prefix[:-1]] = np.asarray(tree)
    return out


def save(path: str, params: Any) -> None:
    path = os.path.abspath(path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    flat = _flatten(params)
    # write-then-rename so a concurrent reader (parallel pytest workers,
    # a serving process hot-loading a trained draft) never sees a torn file
    if not path.endswith(".npz"):
        path += ".npz"        # np.savez appends it; keep tmp/final in sync
    tmp = f"{path}.tmp.{os.getpid()}.npz"
    try:
        np.savez(tmp, **flat)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


class StaleCheckpoint(ValueError):
    """The file's arrays do not match the template: a key is missing or a
    shape differs (the architecture changed under the same name)."""


def load(path: str, template: Any) -> Any:
    """Restore into the structure of ``template`` (dtypes preserved).
    Raises FileNotFoundError for a missing file and StaleCheckpoint for
    one whose keys or shapes do not match ``template``."""
    data = np.load(path)
    flat = {k: data[k] for k in data.files}

    def rebuild(tree: Any, prefix: str = "") -> Any:
        if isinstance(tree, dict):
            return {k: rebuild(v, f"{prefix}{k}.") for k, v in tree.items()}
        if isinstance(tree, list):
            return [rebuild(v, f"{prefix}{i}.") for i, v in enumerate(tree)]
        if isinstance(tree, tuple):
            return tuple(rebuild(v, f"{prefix}{i}.")
                         for i, v in enumerate(tree))
        if tree is None:
            return None
        key = prefix[:-1]
        if key not in flat:
            raise StaleCheckpoint(f"{path}: no array {key!r}")
        arr = flat[key]
        if arr.shape != tree.shape:
            raise StaleCheckpoint(f"{path}: {key!r} has shape {arr.shape}, "
                                  f"expected {tree.shape}")
        return jnp.asarray(arr, dtype=tree.dtype)

    return rebuild(template)
