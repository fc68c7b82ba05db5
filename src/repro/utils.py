"""Small shared utilities: pytree helpers, rng threading, shape math."""
from __future__ import annotations

import math
import os
from pathlib import Path
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# the persistent compile cache lives at one fixed path in the checkout
# (src/repro/utils.py -> repo root): the path is part of the cache key,
# and a directory that moves never hits
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`
    inside the checkout. Call it before the first compile. Returns the
    directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def tree_size(tree: PyTree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_zeros_like(tree: PyTree, dtype=None) -> PyTree:
    return jax.tree.map(lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree)


def tree_finite(tree: PyTree) -> bool:
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    if not leaves:
        return True
    return bool(jnp.all(jnp.stack(leaves)))


def split_keys(key: jax.Array, names: list[str]) -> dict[str, jax.Array]:
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


class RngStream:
    """Deterministic named rng stream: each `.next(name)` is independent."""

    def __init__(self, key: jax.Array):
        self._key = key
        self._count = 0

    def next(self, name: str = "") -> jax.Array:
        self._count += 1
        return jax.random.fold_in(self._key, hash((name, self._count)) % (2**31))


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def flatten_dict(d: dict, prefix: str = "") -> dict[str, Any]:
    out = {}
    for k, v in d.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten_dict(v, path))
        else:
            out[path] = v
    return out


def unflatten_dict(flat: dict[str, Any]) -> dict:
    out: dict = {}
    for path, v in flat.items():
        parts = path.split("/")
        cur = out
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = v
    return out


def tree_paths(tree: PyTree) -> list[tuple[str, Any]]:
    """(path-string, leaf) pairs using '/'-joined dict keys / indices."""
    flat_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for keypath, leaf in flat_with_path:
        parts = []
        for k in keypath:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        out.append(("/".join(parts), leaf))
    return out


def tree_map_with_path(fn: Callable[[str, Any], Any], tree: PyTree) -> PyTree:
    """Map fn(path_str, leaf) -> new leaf over a pytree."""
    def _fn(keypath, leaf):
        parts = []
        for k in keypath:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        return fn("/".join(parts), leaf)
    return jax.tree_util.tree_map_with_path(_fn, tree)
