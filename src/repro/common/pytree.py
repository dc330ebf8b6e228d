"""Pytree helpers used across the framework."""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


def tree_zeros_like(tree: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: Pytree, s) -> Pytree:
    return jax.tree.map(lambda x: x * s, tree)


def tree_weighted_mean(trees: Sequence[Pytree], weights: Sequence[float]) -> Pytree:
    """Weighted parameter average — the FedAvg aggregation primitive."""
    w = np.asarray(weights, dtype=np.float64)
    w = w / w.sum()
    out = tree_scale(trees[0], float(w[0]))
    for t, wi in zip(trees[1:], w[1:]):
        out = jax.tree.map(lambda acc, x, wi=float(wi): acc + wi * x, out, t)
    return out


def tree_stack(trees: Sequence[Pytree]) -> Pytree:
    """Stack homogeneous pytrees along a new leading axis (client axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_unstack(tree: Pytree, n: int) -> list[Pytree]:
    return [jax.tree.map(lambda x, i=i: x[i], tree) for i in range(n)]


def tree_leading_dim(tree: Pytree) -> int:
    """Size of the leading (client) axis of a stacked pytree."""
    return int(jax.tree.leaves(tree)[0].shape[0])


def tree_take(tree: Pytree, idx) -> Pytree:
    """Gather along the leading (client) axis of a stacked pytree."""
    idx = jnp.asarray(idx)
    return jax.tree.map(lambda x: x[idx], tree)


def tree_cat(trees: Sequence[Pytree]) -> Pytree:
    """Concatenate stacked pytrees along the leading (client) axis —
    the buffered-async driver's buffered uploads join through this."""
    if len(trees) == 1:
        return trees[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *trees)


def tree_weighted_mean_stacked(stack: Pytree, weights) -> Pytree:
    """FedAvg aggregation over the leading (client) axis of a stacked
    pytree — one contraction per leaf instead of K sequential adds."""
    w = np.asarray(weights, dtype=np.float64)
    w = jnp.asarray(w / w.sum(), jnp.float32)
    return jax.tree.map(
        lambda x: jnp.tensordot(w, x.astype(jnp.float32),
                                axes=([0], [0])).astype(x.dtype), stack)


def tree_trimmed_mean_stacked(stack: Pytree, weights, trim: int) -> Pytree:
    """Per-coordinate trimmed weighted mean over the leading (client) axis.

    For every scalar coordinate the ``trim`` smallest and ``trim`` largest
    of the K client values are discarded and the survivors averaged with
    their (renormalized) client weights — robust to up to ``trim``
    arbitrarily corrupted uploads per coordinate (docs/robustness.md).

    ``trim == 0`` delegates to :func:`tree_weighted_mean_stacked` so plain
    configs stay *bitwise* identical to FedAvg (a sorted summation would
    reorder the floating-point adds).
    """
    if trim == 0:
        return tree_weighted_mean_stacked(stack, weights)
    k = tree_leading_dim(stack)
    if 2 * trim >= k:
        raise ValueError(f"trim={trim} needs K >= {2 * trim + 1} uploads, "
                         f"got K={k}")
    w = np.asarray(weights, dtype=np.float64)
    w = jnp.asarray(w / w.sum(), jnp.float32)

    def _leaf(x):
        flat = x.astype(jnp.float32).reshape(k, -1)
        order = jnp.argsort(flat, axis=0)                  # [K, D]
        sorted_vals = jnp.take_along_axis(flat, order, axis=0)
        sorted_w = w[order]                                # weight by rank
        keep = jnp.zeros((k, 1), jnp.float32).at[trim:k - trim].set(1.0)
        kept_w = sorted_w * keep
        # zero trimmed slots by where(), not by the 0-weight product: a
        # non-finite value in the trim region (NaN sorts last) would
        # otherwise poison the sum via NaN * 0 = NaN
        kept_vals = jnp.where(keep > 0, sorted_vals, 0.0)
        num = jnp.sum(kept_vals * kept_w, axis=0)
        den = jnp.sum(kept_w, axis=0)
        return (num / den).reshape(x.shape[1:]).astype(x.dtype)

    return jax.tree.map(_leaf, stack)


def tree_coordinate_median_stacked(stack: Pytree, weights) -> Pytree:
    """Per-coordinate weighted median over the leading (client) axis.

    The weighted median is the smallest client value whose cumulative
    (sorted-order) weight reaches half the total — with uniform weights
    and odd K this is the classic coordinate-wise median, robust to
    ``(K-1)//2`` arbitrary uploads per coordinate.
    """
    k = tree_leading_dim(stack)
    w = np.asarray(weights, dtype=np.float64)
    w = jnp.asarray(w / w.sum(), jnp.float32)

    def _leaf(x):
        flat = x.astype(jnp.float32).reshape(k, -1)
        order = jnp.argsort(flat, axis=0)
        sorted_vals = jnp.take_along_axis(flat, order, axis=0)
        cum = jnp.cumsum(w[order], axis=0)
        # first rank whose cumulative weight crosses 0.5 (inclusive)
        idx = jnp.argmax(cum >= 0.5, axis=0)
        med = jnp.take_along_axis(sorted_vals, idx[None, :], axis=0)[0]
        return med.reshape(x.shape[1:]).astype(x.dtype)

    return jax.tree.map(_leaf, stack)


def tree_spec(tree: Pytree) -> list:
    """Flat ``(path, shape, dtype)`` signature of a pytree, for upload
    wire-safety checks (``PopulationManager.push_wave``)."""
    out = []

    def _leaf(path, x):
        dt = x.dtype if hasattr(x, "dtype") else np.asarray(x).dtype
        out.append((path, tuple(np.shape(x)), str(dt)))
        return x

    tree_map_with_path(_leaf, tree)
    return out


def tree_check_like(tree: Pytree, like: Pytree, what: str = "pytree") -> None:
    """Raise ValueError naming the first structural mismatch between
    ``tree`` and the prototype ``like`` (paths, shapes, dtypes)."""
    got, want = tree_spec(tree), tree_spec(like)
    got_paths = [p for p, _, _ in got]
    want_paths = [p for p, _, _ in want]
    if got_paths != want_paths:
        missing = sorted(set(want_paths) - set(got_paths))
        extra = sorted(set(got_paths) - set(want_paths))
        raise ValueError(
            f"{what} structure mismatch: missing leaves {missing[:4]}, "
            f"unexpected leaves {extra[:4]}")
    for (p, gs, gd), (_, ws, wd) in zip(got, want):
        if gs != ws:
            raise ValueError(f"{what} leaf {p!r} has shape {gs}, "
                             f"expected {ws}")
        if gd != wd:
            raise ValueError(f"{what} leaf {p!r} has dtype {gd}, "
                             f"expected {wd}")


def tree_sq_dist(a: Pytree, b: Pytree):
    """sum ||a-b||^2 over all leaves (FedProx proximal term)."""
    d = jax.tree.map(lambda x, y: jnp.sum((x - y) ** 2), a, b)
    return jax.tree.reduce(jnp.add, d)


def tree_count(tree: Pytree) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(tree)))


def tree_bytes(tree: Pytree) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def tree_isfinite(tree: Pytree):
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    return jnp.all(jnp.stack(leaves)) if leaves else jnp.asarray(True)


def tree_cast(tree: Pytree, dtype) -> Pytree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


def tree_map_with_path(fn: Callable, tree: Pytree) -> Pytree:
    """fn(path_str, leaf) -> leaf, path joined with '/'."""

    def _fn(path, leaf):
        keys = []
        for p in path:
            if hasattr(p, "key"):
                keys.append(str(p.key))
            elif hasattr(p, "idx"):
                keys.append(str(p.idx))
            else:
                keys.append(str(p))
        return fn("/".join(keys), leaf)

    return jax.tree_util.tree_map_with_path(_fn, tree)
