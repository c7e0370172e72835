"""Sharding rules: param / optimizer / input / cache PartitionSpecs.

Policy (DESIGN.md §5):
* batch dims        -> ("pod",)+"data" (when divisible),
* attention q/kv projections, FFN hidden, MoE experts, SSM heads, vocab
                    -> "model" (tensor/expert parallel),
* KV-cache sequence dim -> "model" for decode (the cache, not the
  weights, dominates decode memory; softmax over a sharded length lowers
  to cheap max/sum all-reduces),
* optimizer moments -> params' spec + an extra "data" shard on the first
  divisible replicated dim (ZeRO-style), which is what lets 35B-class
  train states fit 16 GB/chip.

Every rule is divisibility-guarded: a dim only gets a mesh axis if its
size divides evenly, so the same rules serve all 10 archs x 4 shapes.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import data_axes

# leaf-name -> which dim gets "model"
_MODEL_AXIS_RULES = {
    # attention / mlp (stacked leaves: +1 for the layer dim)
    "wq": -1, "wk": -1, "wv": -1, "w_gate": -1, "w_up": -1, "w_in": -1,
    "bq": -1, "bk": -1, "bv": -1,
    "wo": -2, "w_down": -2, "w_out": -2,
    # moe: experts dim
    "experts_gate": -3, "experts_up": -3, "experts_down": -3,
    # ssm small tensors: shard heads/channels
    "conv_w": -1, "conv_b": -1, "A_log": -1, "D": -1, "dt_bias": -1,
    "gate_norm": -1,
    # embeddings
    "embed": -2, "lm_head": -1,
}
_REPLICATED = {"w_router", "norm", "attn_norm", "mlp_norm", "cross_norm",
               "final_norm", "enc_norm"}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


# quantized-weight pytree fields: codes/packed shard like their parent
# weight; scales/outliers are small and stay replicated
_QUANT_MAIN_FIELDS = ("codes", "packed")
_QUANT_SIDE_FIELDS = ("scale", "absmax", "outlier_idx", "outlier_w")


def _leaf_spec(path: str, shape, mesh: Mesh) -> P:
    parts = path.split("/")
    name = parts[-1].split(".")[0]
    ndim = len(shape)
    spec = [None] * ndim
    if name in _QUANT_SIDE_FIELDS or ndim == 0:
        return P(*spec)
    if name in _QUANT_MAIN_FIELDS and len(parts) >= 2:
        name = parts[-2].split(".")[0]      # parent weight's rule
    if name in _REPLICATED:
        return P(*spec)
    dim = _MODEL_AXIS_RULES.get(name)
    if dim is None:
        return P(*spec)
    dim = ndim + dim if dim < 0 else dim
    if 0 <= dim < ndim and shape[dim] % _axis_size(mesh, "model") == 0:
        spec[dim] = "model"
    return P(*spec)


def _path_str(kp) -> str:
    parts = []
    for e in kp:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "name"):
            parts.append(str(e.name))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


def param_specs(abstract_params, mesh: Mesh):
    """PartitionSpec tree matching a params pytree (by leaf name)."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: _leaf_spec(_path_str(kp), leaf.shape, mesh),
        abstract_params)


def opt_specs(abstract_opt, pspecs, mesh: Mesh):
    """Optimizer moments: param spec + ZeRO 'data' shard on the first
    replicated dim that divides."""
    dax = "data"
    dsize = _axis_size(mesh, dax)

    def zero_shard(spec: P, leaf) -> P:
        s = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for i, (ax, dim) in enumerate(zip(s, leaf.shape)):
            if ax is None and dim % dsize == 0 and dim >= dsize:
                s[i] = dax
                break
        return P(*s)

    m_specs = jax.tree.map(zero_shard, pspecs,
                           abstract_opt["m"],
                           is_leaf=lambda x: isinstance(x, P))
    return {"m": m_specs,
            "v": jax.tree.map(lambda s: s, m_specs,
                              is_leaf=lambda x: isinstance(x, P)),
            "step": P()}


# ---------------------------------------------------------------------------
# inputs / caches
# ---------------------------------------------------------------------------
def _batch_axes(mesh: Mesh, batch: int):
    dax = data_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in dax]))
    if batch % total == 0:
        return dax
    if batch % mesh.shape["data"] == 0:
        return ("data",)
    return None


def input_specs_sharding(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                         specs: Dict[str, Any]) -> Dict[str, P]:
    b_ax = _batch_axes(mesh, shape.global_batch)
    out = {}
    for k, v in specs.items():
        ndim = len(v.shape)
        s = [None] * ndim
        s[0] = b_ax
        out[k] = P(*s)
    return out


def cache_specs(cfg: ModelConfig, abstract_cache, mesh: Mesh,
                batch: int) -> Dict[str, P]:
    """Decode-cache shardings: batch on data axes, cache length (or SSM
    heads / conv channels) on "model"."""
    b_ax = _batch_axes(mesh, batch)
    msz = _axis_size(mesh, "model")

    def spec_for(key: str, leaf) -> P:
        shp = leaf.shape
        if key in ("k", "v"):                 # (L, B, kv, W, hd)
            w = "model" if shp[3] % msz == 0 else None
            return P(None, b_ax, None, w, None)
        if key in ("shared_k", "shared_v"):   # (sites, B, W, kv, hd)
            w = "model" if shp[2] % msz == 0 else None
            return P(None, b_ax, w, None, None)
        if key in ("enc_k", "enc_v"):         # (L, B, S_enc, kv, hd)
            w = "model" if shp[2] % msz == 0 else None
            return P(None, b_ax, w, None, None)
        if key == "ssm_state":                # (L, B, nh, hd, ds)
            h = "model" if shp[2] % msz == 0 else None
            return P(None, b_ax, h, None, None)
        if key == "conv":                     # (L, B, K-1, C)
            c = "model" if shp[3] % msz == 0 else None
            return P(None, b_ax, None, c)
        if key in ("k_scale", "v_scale"):     # (L, B, kv, W)
            w = "model" if shp[3] % msz == 0 else None
            return P(None, b_ax, None, w)
        if key == "slot_pos":                 # (B, W)
            w = "model" if shp[1] % msz == 0 else None
            return P(b_ax, w)
        if key == "pos":                      # (B,)
            return P(b_ax)
        return P(*([None] * len(shp)))

    return {k: spec_for(k, v) for k, v in abstract_cache.items()}


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
