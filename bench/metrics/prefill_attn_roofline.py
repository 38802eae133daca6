"""prefill_attn_roofline: least time the chip needs for the causal
attention of the window's prompts, streamed in the cell's chunks
(`counts.prefill_attn_need`), over the summed device time of the flash
prefill kernels (`flash_prefill_pallas` / `flash_prefill_chunk_pallas`
in "XLA Ops")."""
import counts
import tracing

KERNEL = r"flash_prefill(_chunk)?_pallas(\.\d+)?$"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = tracing.summed_ns(ctx.trace["devices"][0]["ops"], KERNEL,
                             *ctx.span)
    if not n:
        return None
    from harness import counts_shape
    e = ctx.cell.cell["engine"]
    f, b = counts.prefill_attn_need(ctx.counts_reqs(),
                                    counts_shape(ctx.cell),
                                    e["chunk_len"])
    return counts.roofline_share(f, b, t / 1e9, ctx.peaks)[0]
