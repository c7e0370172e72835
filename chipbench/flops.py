"""Operations and bytes of one kernel call, computed from its shapes:
the yardstick for the roofline shares. Nothing here is read from the
program under test. A model's FLOPs, for ``step_mfu``, are its
architecture's (``chipbench/architectures/<architecture>.py``)."""
from __future__ import annotations

from typing import Tuple


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
