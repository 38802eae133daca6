"""mfu_pct: model FLOPs the window's served requests need
(`counts.model_flops`: 2 x layer-stack params per forwarded token,
causal attention at each token's context, the LM head per served token;
nothing recomputed) over the window's wall time and the chip's bf16
peak."""
import counts


def read(ctx):
    if ctx.peaks is None:
        return None
    from harness import counts_shape
    f = counts.model_flops(ctx.counts_reqs(), counts_shape(ctx.cell))
    return 100.0 * f / (ctx.window_s * ctx.peaks["bf16_flops"]
                        * ctx.device["count"])
