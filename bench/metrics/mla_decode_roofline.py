"""mla_decode_roofline: least time the chip needs for the absorbed MLA
decode attention of the window's requests (`counts.decode_attn_need`
over the architecture's absorbed work, `Work.decode()`: each slot's
valid latent rows, its queries and latent outputs) over the summed
device time of the latent decode kernel (`decode_mla_pallas` in "XLA
Ops")."""
import counts
import tracing

KERNEL = r"decode_mla_pallas(\.\d+)?$"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = tracing.summed_ns(ctx.trace["devices"][0]["ops"], KERNEL,
                             *ctx.span)
    if not n:
        return None
    from harness import counts_shape
    w = counts_shape(ctx.cell)
    if not hasattr(w, "decode"):
        return None
    f, b = counts.decode_attn_need(ctx.counts_reqs(), w.decode())
    return counts.roofline_share(f, b, t / 1e9, ctx.peaks)[0]
