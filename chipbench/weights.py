"""Seeded weights, made by the benchmark and not by the program.

Every float leaf of the program's parameter tree gets its values from
``(seed, leaf index)`` alone, so the reference can make any one leaf again
without the others, layer by layer. Matmul kernels (leaves named ``w``,
``(in, out)``) are normal with standard deviation ``in ** -0.5``,
embeddings normal with 0.02, every other float leaf (norm gains, lerp
vectors) ones. Values are drawn in float32 and rounded once to the served
dtype, so the one jitted call that makes the whole tree and a per-leaf
call give the same bits.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any whole number up to 64 bits (``PRNGKey`` alone keeps
    only the low 32)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def path_names(keypath) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in keypath)


def leaf_std(names: Tuple[str, ...], shape) -> float:
    """Standard deviation of a seeded leaf; 0 marks a leaf of ones."""
    if names[-1] == "w" and len(shape) >= 2:
        return float(shape[-2]) ** -0.5
    if names[-1] == "embedding":
        return 0.02
    return 0.0


def _leaf(key, index, shape, std: float, dtype):
    if std == 0.0:
        return jnp.ones(shape, dtype)
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    return (x * std).astype(dtype)


def leaf_table(shapes) -> List[Tuple[Tuple[str, ...], Any]]:
    """``(names, ShapeDtypeStruct)`` per leaf, in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [(path_names(kp), s) for kp, s in flat]


def make_params(shapes, seed: int, dtype) -> Any:
    """The whole tree in one jitted call, on the default device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [path_names(kp) for kp, _ in flat]
    specs = [(s.shape, s.dtype) for _, s in flat]

    def make(key):
        leaves = []
        for i, (nm, (shape, dt)) in enumerate(zip(names, specs)):
            out_dt = dtype if jnp.issubdtype(dt, jnp.floating) else dt
            leaves.append(_leaf(key, i, shape, leaf_std(nm, shape),
                                out_dt))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)(base_key(seed))


def leaf_maker(shapes, seed: int, dtype) -> Callable[[Tuple[str, ...]], Any]:
    """``get(names)``: that one leaf as :func:`make_params` makes it."""
    table = leaf_table(shapes)
    index: Dict[Tuple[str, ...], int] = {nm: i for i, (nm, _) in
                                         enumerate(table)}
    key = base_key(seed)
    fns: Dict[Tuple, Callable] = {}

    def get(names):
        i = index[tuple(names)]
        s = table[i][1]
        sig = (tuple(s.shape), leaf_std(tuple(names), s.shape))
        if sig not in fns:
            fns[sig] = jax.jit(_leaf, static_argnums=(2, 3, 4))
        return fns[sig](key, i, sig[0], sig[1], dtype)

    return get
