"""Small shared utilities: PRNG splitting by path, tree helpers, dtypes."""
from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def tree_bytes(tree: Any) -> int:
    """Total on-device bytes of a pytree of arrays / ShapeDtypeStructs."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for x in leaves:
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            total += int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
    return total


def tree_count(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(x.shape)) for x in leaves if hasattr(x, "shape"))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:,.2f} {unit}"
        n /= 1024.0
    return f"{n:,.2f} PiB"


def human_flops(n: float) -> str:
    for unit in ("F", "KF", "MF", "GF", "TF", "PF"):
        if abs(n) < 1000.0:
            return f"{n:,.2f} {unit}"
        n /= 1000.0
    return f"{n:,.2f} EF"


class KeyGen:
    """Deterministic named PRNG key dispenser (stable across refactors)."""

    def __init__(self, seed: int | jax.Array):
        self._root = jax.random.key(seed) if isinstance(seed, int) else seed

    def __call__(self, name: str) -> jax.Array:
        return jax.random.fold_in(self._root, _stable_hash(name))


def _stable_hash(s: str) -> int:
    h = 2166136261
    for c in s.encode():
        h = (h ^ c) * 16777619 % (1 << 31)
    return h


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def assert_no_nans(tree: Any, where: str = "") -> None:
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            if bool(jnp.any(~jnp.isfinite(leaf))):
                raise AssertionError(
                    f"non-finite values in {jax.tree_util.keystr(path)} {where}"
                )


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called at the start of each entry point's `main()`, never at import.
    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to `.jax_cache` at the
    root of the checkout (gitignored): a fixed path, so a later run finds
    what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
