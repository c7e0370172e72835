"""Operations and bytes computed from shapes: the yardstick for the
roofline shares and the model FLOP utilization. Nothing here is read
from the program under test; the shapes come from the configuration
file and the cell's settings."""
from __future__ import annotations

from typing import Dict, Tuple


def dims(config: Dict) -> Dict[str, int]:
    """The widths of a dense decoder configuration file."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "d": d,
        "heads": h,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or d // h),
        "ff": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
    }


def layer_matmuls(config: Dict) -> Dict[str, Tuple[int, int]]:
    """(in, out) of every projection of one decoder layer."""
    m = dims(config)
    q = m["heads"] * m["head_dim"]
    kv = m["kv_heads"] * m["head_dim"]
    return {"wq": (m["d"], q), "wk": (m["d"], kv), "wv": (m["d"], kv),
            "wo": (q, m["d"]), "w_gate": (m["d"], m["ff"]),
            "w_up": (m["d"], m["ff"]), "w_down": (m["ff"], m["d"])}


def matmul_params(config: Dict) -> int:
    """Weights multiplied per token: every layer's projections and the
    output head (the embedding is a lookup, not a matmul)."""
    m = dims(config)
    per_layer = sum(k * n for k, n in layer_matmuls(config).values())
    return m["layers"] * per_layer + m["d"] * m["vocab"]


def attention_flops(config: Dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context``
    cached positions, all layers: 2 x 2 x heads x head_dim x context."""
    m = dims(config)
    return 4 * m["layers"] * m["heads"] * m["head_dim"] * context


def decode_token_flops(config: Dict, context: int) -> int:
    """Model FLOPs of one decoded token that attends ``context``
    positions (its own included)."""
    return 2 * matmul_params(config) + attention_flops(config, context)


def decode_flops(config: Dict, prompt_len: int, generated: int) -> int:
    """Model FLOPs of the decode steps that produced tokens 2 ..
    ``generated`` of a request (the first comes from prefill): decode
    step k attends ``prompt_len + k`` positions."""
    n = max(generated - 1, 0)
    return (n * 2 * matmul_params(config)
            + attention_flops(config, 1) * (n * prompt_len
                                             + n * (n + 1) // 2))


def prefill_flops(config: Dict, prompt_len: int) -> int:
    """Model FLOPs of prefilling one prompt: every position through the
    layers with causal attention, and the output head at the last
    position only (the one whose logits are served). Padding is not
    model work and is not counted."""
    m = dims(config)
    layer_params = matmul_params(config) - m["d"] * m["vocab"]
    causal = prompt_len * (prompt_len + 1) // 2
    return (2 * layer_params * prompt_len
            + 4 * m["layers"] * m["heads"] * m["head_dim"] * causal
            + 2 * m["d"] * m["vocab"])


def int8_matmul_cost(m: int, k: int, n: int) -> Tuple[int, int]:
    """(operations, bytes) of one int8-weight matmul ``x (m, k) @
    dequant(codes (k, n), scale (n,))`` with bf16 activations in and
    out: 2mnk operations; the codes, the scales, the activations and
    the output each moved once."""
    return 2 * m * n * k, k * n + 4 * n + 2 * m * k + 2 * m * n


def roofline_seconds(ops: float, nbytes: float, ops_per_s: float,
                     bytes_per_s: float) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_bytes = ops / ops_per_s, nbytes / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
