"""Seeded weights, made by the benchmark and not by the program.

Every leaf is drawn from its own key, ``fold_in(seed key, crc32(path))``,
and a leaf stacked over layers draws layer ``l`` from
``fold_in(leaf key, l)``. So the serving path gets the whole tree from
one jitted call on the device, in the type it is served in, while the
reference regenerates one layer at a time (:func:`layer`) and gets the
same numbers. The tree's layout (paths, shapes, dtypes) is read from
``jax.eval_shape`` of the model's ``init``; its values never are.

Draws: projections N(0, 1/fan_in); the embedding N(0, 1); norm gains
1 + 0.1 N(0, 1); biases 0.1 N(0, 1).
"""
from __future__ import annotations

import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

STACKED = "layers"


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed; every bit of a seed
    wider than 32 bits counts."""
    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jnp.asarray(state, dtype=jnp.uint32)


def _draw(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        v = 1.0 + 0.1 * z
    elif name in ("bq", "bk", "bv"):
        v = 0.1 * z
    elif name == "embed":
        v = z
    else:
        v = z * (shape[-2] ** -0.5)
    return v.astype(dtype)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()))


def _paths(tree, prefix=""):
    """(path, leaf) pairs of a nested dict of ShapeDtypeStructs."""
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _paths(v, p)
        else:
            yield p, v


def put(tree: Dict, path: str, value) -> None:
    """Set ``value`` at the slash-separated ``path`` of a nested dict."""
    *head, last = path.split("/")
    for k in head:
        tree = tree.setdefault(k, {})
    tree[last] = value


def layout(model) -> Dict[str, Any]:
    """Paths, shapes and dtypes of the model's parameter tree."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return dict(_paths(shapes))


def make(model, key) -> Dict[str, Any]:
    """The whole parameter tree, in one jitted call on the device."""
    lay = layout(model)

    def build(key):
        out: Dict[str, Any] = {}
        for path, sd in lay.items():
            k = _leaf_key(key, path)
            name = path.split("/")[-1]
            if path.startswith(STACKED + "/"):
                n = sd.shape[0]
                v = jax.vmap(lambda i: _draw(jax.random.fold_in(k, i),
                                             name, sd.shape[1:],
                                             sd.dtype))(jnp.arange(n))
            else:
                v = _draw(k, name, sd.shape, sd.dtype)
            put(out, path, v)
        return out

    return jax.jit(build)(key)


def layer(lay: Dict[str, Any], key, index: int) -> Dict[str, jax.Array]:
    """Layer ``index`` of every stacked leaf, as served (in the leaf's
    dtype), keyed by the path below ``layers/``."""
    out = {}
    for path, sd in lay.items():
        if not path.startswith(STACKED + "/"):
            continue
        k = jax.random.fold_in(_leaf_key(key, path), index)
        out[path[len(STACKED) + 1:]] = _draw(k, path.split("/")[-1],
                                             sd.shape[1:], sd.dtype)
    return out


def leaf(lay: Dict[str, Any], key, path: str) -> jax.Array:
    """A leaf that is not stacked over layers, as served."""
    sd = lay[path]
    return _draw(_leaf_key(key, path), path.split("/")[-1], sd.shape,
                 sd.dtype)
