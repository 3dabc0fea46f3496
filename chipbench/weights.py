"""Random weights from the run's seed, the same for the program and for the
reference.

A family module lists its leaves as ``Leaf`` entries.  Each leaf, and
each layer of a stacked leaf, has its own key, ``fold_in(fold_in(root,
leaf index), layer)``, so the reference can make one layer's weights
again, bit for bit, without holding the rest.  The program's tree is made
on the device by one jitted call, in the dtype it is served in.
"""

from __future__ import annotations

import math
from typing import Any
from typing import Callable
from typing import Dict
from typing import List
from typing import NamedTuple
from typing import Tuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    path: str                  # "/"-separated path in the program's tree
    shape: Tuple[int, ...]     # one layer's shape
    dtype: Any
    init: Tuple                # see _values
    stacked: bool = True       # one slice per layer


def _values(key, shape, init) -> jax.Array:
    """float32 values of one leaf.

    ("std", s):            uniform with standard deviation s
    ("one_pm", w):         1 + uniform(-w, w)
    ("uniform", a, b):     uniform(a, b)
    ("log_uniform", a, b): exp(uniform(log a, log b))
    ("softplus_inv_log_uniform", a, b): the bias whose softplus is
                           log-uniform in [a, b]
    """
    kind = init[0]
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    if kind == "std":
        return u * (math.sqrt(3.0) * init[1])
    if kind == "one_pm":
        return 1.0 + u * init[1]
    if kind == "uniform":
        return init[1] + (u + 1.0) * 0.5 * (init[2] - init[1])
    lo, hi = math.log(init[1]), math.log(init[2])
    v = jnp.exp(lo + (u + 1.0) * 0.5 * (hi - lo))
    if kind == "log_uniform":
        return v
    if kind == "softplus_inv_log_uniform":
        return v + jnp.log(-jnp.expm1(-v))
    raise ValueError(f"unknown init {init!r}")


def leaf_values(key, leaves: List[Leaf], i: int, layer: int) -> jax.Array:
    """Leaf ``i`` (one layer of it) in its served dtype."""
    lf = leaves[i]
    k = jax.random.fold_in(jax.random.fold_in(key, i), layer)
    return _values(k, lf.shape, lf.init).astype(lf.dtype)


def _set(tree: Dict, path: str, value) -> None:
    *parents, last = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[last] = value


def program_params(key, leaves: List[Leaf], n_layers: int,
                   finish: Callable[[Dict], Dict] = lambda t: t) -> Dict:
    """The program's parameter tree, made by one jitted call on the
    default device.  ``finish`` adds leaves derived from others (a tied
    head)."""
    def make(key):
        tree: Dict = {}
        for i, lf in enumerate(leaves):
            if lf.stacked:
                v = jax.vmap(lambda layer, i=i: leaf_values(
                    key, leaves, i, layer))(jnp.arange(n_layers))
            else:
                v = leaf_values(key, leaves, i, 0)
            _set(tree, lf.path, v)
        return finish(tree)
    return jax.jit(make)(key)


def index(leaves: List[Leaf]) -> Dict[str, int]:
    return {lf.path: i for i, lf in enumerate(leaves)}
