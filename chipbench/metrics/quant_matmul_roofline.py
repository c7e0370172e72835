"""Share of its roofline that the int8 ``quant_matmul`` kernel reached
in the traced slice, in percent: the sum over its calls of the least
time each could take, over the sum of the time each took (trace).

A call's least time is the larger of its operations at the chip's bf16
peak (the kernel dequantizes each int8 tile to bf16 and multiplies on
the MXU in bf16) and its bytes at the HBM peak, both computed from the
call's own shapes (chipbench/flops.py). Decode calls (M = the batch's
lanes) are bound by memory; prefill calls (M = rows x padded length)
by compute. None when no call of the kernel was traced."""
from chipbench import flops


def read(ctx):
    calls = [] if ctx.trace is None else ctx.trace.int8_kernel
    if not calls:
        return None
    least = 0.0
    for m, k, n, _ in calls:
        ops, nbytes = flops.int8_matmul_cost(m, k, n)
        least += flops.roofline_seconds(ops, nbytes,
                                        ctx.peaks["bf16_flops_per_s"],
                                        ctx.peaks["hbm_bytes_per_s"])[0]
    return 100.0 * least / sum(c[3] for c in calls)
