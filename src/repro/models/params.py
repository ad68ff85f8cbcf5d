"""Parameter construction: arrays + logical sharding axes from one source.

``Maker`` builds a nested dict of parameters and, in lockstep, a nested dict
of logical-axis tuples (the sharding specs the dist layer resolves against a
mesh). With ``abstract=True`` it produces ShapeDtypeStructs — the dry-run
path; nothing is allocated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class Maker:
    def __init__(self, key, param_dtype=jnp.float32, abstract: bool = False):
        self._key = key
        self.dtype = param_dtype
        self.abstract = abstract
        self.specs: dict = {}

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    def param(self, shape, axes, scale: float | str = "fan_in"):
        assert len(shape) == len(axes), f"{shape} vs {axes}"
        if self.abstract:
            arr = jax.ShapeDtypeStruct(tuple(shape), self.dtype)
        else:
            if scale == "fan_in":
                scale = 1.0 / np.sqrt(max(shape[0], 1))
            elif scale == "zeros":
                scale = 0.0
            if scale == 0.0:
                arr = jnp.zeros(shape, self.dtype)
            else:
                arr = (
                    jax.random.normal(self._next_key(), shape, jnp.float32) * scale
                ).astype(self.dtype)
        return arr, tuple(axes)


def split_tree(tree):
    """Nested dict of (array, axes) -> (params, specs)."""
    if isinstance(tree, dict):
        params, specs = {}, {}
        for k, v in tree.items():
            params[k], specs[k] = split_tree(v)
        return params, specs
    if isinstance(tree, (list,)):
        pairs = [split_tree(v) for v in tree]
        return [p for p, _ in pairs], [s for _, s in pairs]
    arr, axes = tree
    return arr, axes


def stack_layers(maker_fn, n_layers: int):
    """Build n_layers copies of a layer's (array, axes) tree, stacked on a
    leading 'layers' axis — the scan-over-layers representation."""

    def stack(trees):
        first = trees[0]
        if isinstance(first, dict):
            # pop, so each leaf's per-layer arrays are freed once stacked:
            # device memory peaks at the per-layer arrays plus one stacked
            # leaf, not at twice the model
            return {k: stack([t.pop(k) for t in trees]) for k in list(first)}
        arrs = [t[0] for t in trees]
        axes = ("layers",) + first[1]
        if isinstance(arrs[0], jax.ShapeDtypeStruct):
            s = arrs[0]
            return jax.ShapeDtypeStruct((len(arrs),) + tuple(s.shape), s.dtype), axes
        return jnp.stack(arrs), axes

    return stack([maker_fn(i) for i in range(n_layers)])


def count_params(params) -> int:
    leaves = jax.tree.leaves(params)
    return sum(int(np.prod(l.shape)) for l in leaves)


def cast_tree(params, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), params)
