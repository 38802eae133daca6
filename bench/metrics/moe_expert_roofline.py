"""moe_expert_roofline: least time the chip needs for the routed
experts' grouped matmuls of the window (`Work.expert_need` over the
jobs' `moe` counters, which count the rows that serve a request, not
a decode step's empty slots: 6 * d_model * d_expert FLOPs an
assignment, each hit expert's three bf16 matrices read once a call,
the assignments' rows in and out) over the summed device time of the
grouped-matmul kernel (`moe_gmm` in "XLA Ops"), prompt segments and
decode steps alike."""
import counts
import tracing

KERNEL = r"moe_gmm(\.\d+)?$"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    moe = [getattr(res, "moe", None) for _, res in ctx.jobs]
    if not moe or any(m is None for m in moe):
        return None
    t, n = tracing.summed_ns(ctx.trace["devices"][0]["ops"], KERNEL,
                             *ctx.span)
    if not n:
        return None
    from harness import counts_shape
    f, b = counts_shape(ctx.cell).expert_need(
        sum(m["assignments"] for m in moe),
        sum(m["experts_hit"] for m in moe))
    return counts.roofline_share(f, b, t / 1e9, ctx.peaks)[0]
