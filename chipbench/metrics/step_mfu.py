"""Model FLOPs of the work served in the window, over the window's
length times the chip's bf16 peak, in percent. Prefill counts each
prompt prefilled in the window; decode counts every token produced by
a decode step, at the context it attended. FLOPs come from the
configuration's shapes, counted by its architecture
(chipbench/architectures/<architecture>.py), not from the program."""


def read(ctx):
    rec, cfg, arch = ctx.records, ctx.cell.config, ctx.cell.arch
    total = 0
    for r in rec.requests:
        if r.req_id not in rec.first:
            continue
        total += (arch.prefill_flops(cfg, r.prompt_len)
                  + arch.decode_flops(cfg, r.prompt_len, len(r.generated)))
    if not total:
        return None
    return 100.0 * total / (rec.window_s * ctx.peaks["bf16_flops_per_s"])
