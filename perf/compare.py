"""The numbers that decide ``correct`` for a training cell.

A training cell compares, with its reference, each checked round's loss and
the per-leaf norm of the parameters' change after the first round and after
the last checked one. A change is judged by the worst leaf: the gap between
the program's norm and the reference's, over the reference's norm of that
leaf or of the median leaf, whichever is larger (some leaves barely move).
Where every client holds its own model, each client's leaf counts as a
leaf: a norm pooled over the clients would average away what a fault does
to each client's update. Leaves whose first reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out.
"""
from __future__ import annotations

import numpy as np

ROUND_OFF_SHARE = 1e-3


def _norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)).reshape(
            x.shape[0], -1), axis=1))
        for x in jax.tree.leaves(tree)])


def client_norms(tree) -> np.ndarray:
    """(leaves, clients): the float32 norm of each client's share of each
    leaf of a stacked tree (clients on axis 0)."""
    import jax
    return np.asarray(jax.jit(_norms)(tree), np.float64)


def leaf_diff_norms(p, p0) -> np.ndarray:
    """(leaves, clients): the norm of each client's ``p - p0``, leaf by
    leaf."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def diff_norms(p, p0):
        return _norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, p0))

    return np.asarray(diff_norms(p, p0), np.float64)


def moving_leaves(grad_norms) -> list[bool]:
    g = np.asarray(grad_norms, np.float64)
    return list(g >= ROUND_OFF_SHARE * np.median(g))


def worst_leaf_gap(got, want, keep) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    k = np.asarray(keep, bool)
    floor = np.median(want[k])
    return float(np.max(np.abs(got[k] - want[k])
                        / np.maximum(want[k], floor)))


def rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want) / np.abs(want)))
