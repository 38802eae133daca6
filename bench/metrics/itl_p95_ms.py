"""itl_p95_ms: 95th percentile of every gap between consecutive tokens of
one request, pooled over all requests of all jobs in the window (host
clock at which the engine records each token)."""
import numpy as np


def read(ctx):
    gaps = [np.diff(r.token_times) for _, r in ctx.served()
            if len(r.token_times) > 1]
    if not gaps:
        return None
    return 1e3 * float(np.percentile(np.concatenate(gaps), 95))
