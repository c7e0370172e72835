"""Top-k Mixture-of-Experts FFN with capacity-based dispatch.

TPU-native design: tokens are sorted by expert id and
scattered into a dense (experts, capacity, d_model) buffer, experts run as
one batched einsum, and results gather back. Under pjit with experts
sharded on the ``model`` axis this induces the canonical all-to-all;
FLOPs equal tokens x top_k x expert_ffn (never tokens x n_experts).

Capacity overflow drops tokens (standard Switch/GShard semantics); the
router aux losses (load-balance + z-loss) push assignment toward uniform
so drops vanish as training proceeds.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.precision import PrecisionPolicy
from repro.quant.apply import linear_apply

# Expert-parallel context: when a production mesh is active (set by the
# launcher around tracing), moe_ffn routes through the shard_map
# expert-parallel implementation below.
_EP_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "moe_expert_parallel", default=None)


@contextlib.contextmanager
def expert_parallel(mesh, data_axes=("data",), model_axis="model"):
    tok = _EP_CTX.set((mesh, tuple(data_axes), model_axis))
    try:
        yield
    finally:
        _EP_CTX.reset(tok)


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float = 1.25) -> int:
    c = int(capacity_factor * n_tokens * top_k / n_experts)
    return max(8, ((c + 7) // 8) * 8)   # multiple of 8 for TPU sublanes


def moe_ffn(p: Dict[str, Any], x: jnp.ndarray, *, top_k: int,
            policy: PrecisionPolicy,
            capacity_factor: float = 1.25
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (T, D) -> (T, D), plus router aux metrics.

    p: {"w_router": (D, E), "experts_gate"/"experts_up": (E, D, F),
        "experts_down": (E, F, D)}

    Under an :func:`expert_parallel` context this dispatches to the
    shard_map expert-parallel path; otherwise (single device, smoke
    tests) it runs the plain sort/scatter implementation.
    """
    ep = _EP_CTX.get()
    if ep is not None:
        mesh, dax, max_ = ep
        E = p["w_router"].shape[-1]
        if (E % mesh.shape[max_] == 0
                and isinstance(p["experts_gate"], jnp.ndarray)):
            return _moe_ffn_expert_parallel(
                p, x, top_k=top_k, policy=policy,
                capacity_factor=capacity_factor, mesh=mesh,
                data_axes=dax, model_axis=max_)
    return _moe_ffn_local(p, x, top_k=top_k, policy=policy,
                          capacity_factor=capacity_factor)


def _moe_ffn_local(p: Dict[str, Any], x: jnp.ndarray, *, top_k: int,
                   policy: PrecisionPolicy,
                   capacity_factor: float = 1.25
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    T, D = x.shape
    E = p["w_router"].shape[-1]
    C = expert_capacity(T, E, top_k, capacity_factor)

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)       # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- flatten assignments and sort by expert ----------------------
    flat_expert = expert_ids.reshape(-1)                      # (T*k,)
    flat_token = jnp.repeat(jnp.arange(T), top_k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    # position within the expert's run
    run_start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos_in_expert = jnp.arange(T * top_k) - run_start[se]
    keep = pos_in_expert < C
    slot = jnp.where(keep, se * C + pos_in_expert, E * C)     # E*C = trash

    # ---- dispatch -----------------------------------------------------
    buf = jnp.zeros((E * C + 1, D), x.dtype)
    buf = buf.at[slot].set(x[st] * keep[:, None].astype(x.dtype))
    buf = buf[:E * C].reshape(E, C, D)

    # ---- expert compute (batched over E) ------------------------------
    cd = policy.compute_dtype
    gate_w = _expert_dense(p["experts_gate"], buf, policy)
    up_w = _expert_dense(p["experts_up"], buf, policy)
    h = jax.nn.silu(gate_w) * up_w
    out_e = _expert_dense(p["experts_down"], h, policy)        # (E, C, D)

    # ---- combine -------------------------------------------------------
    out_flat = out_e.reshape(E * C, D)
    gathered = jnp.where(keep[:, None], out_flat[jnp.minimum(slot, E * C - 1)],
                         0.0).astype(jnp.float32)
    y = jnp.zeros((T, D), jnp.float32)
    y = y.at[st].add(gathered * sg[:, None])
    y = y.astype(cd)

    # ---- aux metrics (Switch load-balance + router z-loss) -------------
    me = jnp.mean(probs, axis=0)                               # (E,)
    one_hot = jax.nn.one_hot(expert_ids[:, 0], E)              # top-1 share
    ce = jnp.mean(one_hot, axis=0)
    aux = {
        "load_balance_loss": E * jnp.sum(me * ce),
        "router_z_loss": jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "dropped_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y, aux


def _expert_dense(w: Any, x: jnp.ndarray,
                  policy: PrecisionPolicy) -> jnp.ndarray:
    """Batched per-expert matmul: w (E, in, out) [possibly quantized],
    x (E, C, in) -> (E, C, out)."""
    return jax.vmap(lambda wi, xi: linear_apply(wi, xi, policy))(w, x)


# ---------------------------------------------------------------------------
# expert-parallel shard_map path
#
# The sort/scatter dispatch above is correct but not SPMD-partitionable
# across (tokens x experts): XLA falls back to replicating the dense
# (E*C, D) dispatch buffers, i.e. activation-sized all-gathers per MoE
# layer. Here the communication pattern is made explicit instead:
#
#   * tokens stay sharded on the data axes and REPLICATED across
#     "model" (they already are — activations are P(data, None));
#   * every model-rank runs the identical local routing for its token
#     block, then computes ONLY its E/m experts (weights are sharded
#     P("model", ...) — expert parallelism);
#   * the partial combine is summed with one psum over "model": the
#     per-layer collective drops from O(E*C*D) gathered bytes to one
#     (T_loc, D) all-reduce.
# ---------------------------------------------------------------------------
def _moe_ffn_expert_parallel(p: Dict[str, Any], x: jnp.ndarray, *,
                             top_k: int, policy: PrecisionPolicy,
                             capacity_factor: float, mesh,
                             data_axes: Tuple[str, ...],
                             model_axis: str
                             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    T, D = x.shape
    E = p["w_router"].shape[-1]
    m = mesh.shape[model_axis]
    E_loc = E // m
    d_shards = 1
    for a in data_axes:
        d_shards *= mesh.shape[a]
    if T % d_shards:
        return _moe_ffn_local(p, x, top_k=top_k, policy=policy,
                              capacity_factor=capacity_factor)
    T_loc = T // d_shards
    C = expert_capacity(T_loc, E, top_k, capacity_factor)
    dspec = data_axes if len(data_axes) > 1 else data_axes[0]

    def body(wr, wg, wu, wd, x_loc):
        # identical local routing on every model-rank (deterministic)
        logits = jnp.einsum("td,de->te", x_loc.astype(jnp.float32),
                            wr.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1,
                                        keepdims=True)
        flat_expert = expert_ids.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(T_loc), top_k)
        flat_gate = gate_vals.reshape(-1)
        order = jnp.argsort(flat_expert)
        se, st, sg = (flat_expert[order], flat_token[order],
                      flat_gate[order])
        run_start = jnp.searchsorted(se, jnp.arange(E), side="left")
        pos = jnp.arange(T_loc * top_k) - run_start[se]
        keep = pos < C
        slot = jnp.where(keep, se * C + pos, E * C)
        buf = jnp.zeros((E * C + 1, D), x_loc.dtype)
        buf = buf.at[slot].set(x_loc[st]
                               * keep[:, None].astype(x_loc.dtype))
        buf = buf[:E * C].reshape(E, C, D)
        # ---- this rank's experts only (expert parallelism) ----------
        ridx = jax.lax.axis_index(model_axis)
        my = jax.lax.dynamic_slice(buf, (ridx * E_loc, 0, 0),
                                   (E_loc, C, D))
        h = jax.nn.silu(_expert_dense(wg, my, policy)) \
            * _expert_dense(wu, my, policy)
        out_loc = _expert_dense(wd, h, policy)          # (E_loc, C, D)
        # keep the big dispatch/combine intermediates in the compute
        # dtype — the (E, C, D) and (T*k, D) f32 buffers dominated the
        # per-chip temp footprint (§Perf H1 iteration 4 memory fix);
        # only the final token accumulator stays f32.
        cd = policy.compute_dtype
        out = jnp.zeros((E, C, D), cd)
        out = jax.lax.dynamic_update_slice(
            out, out_loc.astype(cd), (ridx * E_loc, 0, 0))
        # ---- combine (partial: only local experts filled) -----------
        out_flat = out.reshape(E * C, D)
        gathered = jnp.where(
            keep[:, None], out_flat[jnp.minimum(slot, E * C - 1)],
            jnp.zeros((), cd))
        y = jnp.zeros((T_loc, D), jnp.float32)
        y = y.at[st].add(gathered.astype(jnp.float32) * sg[:, None])
        # combine all-reduce in bf16 — halves the dominant collective;
        # accumulation already happened locally in f32, so only the
        # final rounding is affected (§Perf H1 iteration 2)
        y = jax.lax.psum(y.astype(policy.compute_dtype), model_axis)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E), axis=0)
        aux = jnp.stack([
            E * jnp.sum(me * ce),
            jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
            1.0 - jnp.mean(keep.astype(jnp.float32)),
        ])
        aux = jax.lax.pmean(aux, data_axes)
        return y, aux

    y, aux_v = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None),                  # router replicated
                  P(model_axis, None, None),      # experts sharded
                  P(model_axis, None, None),
                  P(model_axis, None, None),
                  P(dspec, None)),                # tokens on data axes
        out_specs=(P(dspec, None), P()),
        check_vma=False,
    )(p["w_router"], p["experts_gate"], p["experts_up"],
      p["experts_down"], x)
    aux = {"load_balance_loss": aux_v[0], "router_z_loss": aux_v[1],
           "dropped_fraction": aux_v[2]}
    return y, aux
