"""Small shared utilities: PRNG discipline, pytree helpers, timers."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.timers import timed_block


def key_for(seed: int, *path: Any) -> jax.Array:
    """Deterministic named PRNG keys: fold a readable path into a seed.

    Workers can reproduce any stream from (seed, path) — the basis of the
    deterministic-resharding fault-tolerance story (DESIGN.md §4).
    """
    k = jax.random.PRNGKey(seed)
    for p in path:
        h = np.uint32(abs(hash(str(p))) % (2**31 - 1))
        k = jax.random.fold_in(k, h)
    return k


def tree_size_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "size"))


def tree_param_count(tree: Any) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def cast_tree(tree: Any, dtype) -> Any:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


# the persistent compile cache's fallback home: fixed, so its entries
# are found again by the next process (the path is part of the key)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself,
    so no other directory is configured); otherwise the cache lives in
    ``.jax_cache/`` at the repository root."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def timed(store: Dict[str, float], name: str):
    """Accumulating timer; delegates to the obs timer helper so every
    duration in the repo reads the same injectable clock."""
    return timed_block(store, name)


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f}{unit}"
        n /= 1024
    return f"{n:.2f}PiB"


def ceil_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
