"""Group-level importance estimation (paper Eq. 1 + App. A.4/A.5).

    s_{i,j} = Norm_{CC_l in g_i}( { AGG( S(θ_k), ∀θ_k in CC_j ) } )

``S`` is any per-weight criterion (L1/L2 magnitude, SNIP ``|g·θ|``, GraSP
``-θ·Hg``, CroP ``|θ·Hg|``, random); ``AGG`` collapses a coupled-channel
set to one score; ``Norm`` makes scores comparable across groups.  The
grouping engine supplies the coupled-channel sets, so *any* unstructured
criterion becomes a grouped structured one — the paper's "prune any time"
mechanism.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import tree_util as jtu

from repro.core.graph import keystr
from repro.core.groups import Group

CRITERIA = ("l1", "l2", "magnitude", "snip", "grasp", "crop", "random")
AGGS = ("mean", "sum", "max", "l2")
NORMS = ("mean", "sum", "max", "gaussian", "none")


def hessian_grad_product(loss_fn, params, *args):
    """Hg where g = ∇loss — one jvp over the gradient function (GraSP/CroP)."""
    grad_fn = jax.grad(loss_fn)
    g = grad_fn(params, *args)
    _, hg = jax.jvp(lambda p: grad_fn(p, *args), (params,), (g,))
    return g, hg


def leaf_scores(params, criterion: str, grads=None, hg=None, seed: int = 0):
    """Per-weight importance S(θ) as a pytree of f32 host arrays.

    Scored one leaf at a time and moved to the host at once: an f32 copy
    of a bf16 model is twice its size, and holding it whole on the device
    next to the weights would not fit a chip that holds the model."""
    f32 = lambda x: x.astype(jnp.float32)
    extra: tuple = ()
    if criterion in ("l1", "magnitude"):
        fn = lambda t: jnp.abs(f32(t))
    elif criterion == "l2":
        fn = lambda t: jnp.square(f32(t))
    elif criterion == "snip":
        assert grads is not None, "snip needs grads"
        fn, extra = (lambda t, g: jnp.abs(f32(t) * f32(g))), (grads,)
    elif criterion == "grasp":
        assert hg is not None, "grasp needs Hg"
        # lower score = better to KEEP removing? GraSP scores: -θ·Hg; we prune
        # the *lowest* importance, so negate to match "high = keep".
        fn, extra = (lambda t, h: -(f32(t) * f32(h))), (hg,)
    elif criterion == "crop":
        assert hg is not None, "crop needs Hg"
        fn, extra = (lambda t, h: jnp.abs(f32(t) * f32(h))), (hg,)
    elif criterion == "random":
        leaves, treedef = jtu.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        fn = lambda t, k: jax.random.uniform(k, t.shape)
        extra = (jtu.tree_unflatten(treedef, list(keys)),)
    else:
        raise ValueError(f"unknown criterion {criterion!r}")
    return jax.tree.map(lambda *xs: np.asarray(fn(*xs)), params, *extra)


def _axis_scores(leaf: np.ndarray, axis: int) -> np.ndarray:
    """(per-position summed score, count) along one axis."""
    other = tuple(a for a in range(leaf.ndim) if a != axis)
    return leaf.sum(axis=other)


def unit_scores(groups: list[Group], scores, agg: str = "mean",
                norm: str = "mean") -> dict[str, np.ndarray]:
    """Eq. 1: per-group arrays of unit scores (len == n_units)."""
    flat, _ = jtu.tree_flatten_with_path(scores)
    by_path = {keystr(p): np.asarray(l)
               for p, l in flat}

    out: dict[str, np.ndarray] = {}
    for gr in groups:
        # cache per-(path, axis) position sums/counts
        cache: dict[tuple[str, int], tuple[np.ndarray, int]] = {}
        for sl in gr.units[0].slices:
            leaf = by_path[sl.path]
            other = tuple(a for a in range(leaf.ndim) if a != sl.axis)
            if agg == "max":
                red = leaf.max(axis=other) if other else leaf
            elif agg == "l2":
                red = np.square(leaf).sum(axis=other) if other else np.square(leaf)
            else:
                red = leaf.sum(axis=other) if other else leaf
            cnt = int(np.prod([leaf.shape[a] for a in other])) if other else 1
            cache[(sl.path, sl.axis)] = (red, cnt)

        vals = np.zeros(gr.n_units, np.float64)
        counts = np.zeros(gr.n_units, np.float64)
        for u, cc in enumerate(gr.units):
            for sl in cc.slices:
                red, cnt = cache[(sl.path, sl.axis)]
                pos = np.asarray(sl.positions)
                if agg == "max":
                    vals[u] = max(vals[u], float(red[pos].max()))
                else:
                    vals[u] += float(red[pos].sum())
                counts[u] += cnt * len(pos)
        if agg == "mean":
            vals = vals / np.maximum(counts, 1)
        elif agg == "l2":
            vals = np.sqrt(vals)

        if norm == "sum":
            vals = vals / max(vals.sum(), 1e-12)
        elif norm == "mean":
            vals = vals / max(vals.mean(), 1e-12)
        elif norm == "max":
            vals = vals / max(vals.max(), 1e-12)
        elif norm == "gaussian":
            vals = (vals - vals.mean()) / max(vals.std(), 1e-12)
        out[gr.key] = vals
    return out
