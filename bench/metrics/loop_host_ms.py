"""loop_host_ms: host self time of one engine-loop iteration — the
loop's wall time less the time it sat blocked in token fetches, over its
iterations (`ContinuousGenerationResult.loop_seconds`, `fetch_seconds`,
`loop_iters`: the program's own phase clock, the sum of its `step` spans
less their `fetch` children), over the window's jobs. None where the
program does not time its loop."""


def read(ctx):
    jobs = [res for _, res in ctx.jobs]
    if not jobs or not all(hasattr(res, "loop_iters") for res in jobs):
        return None
    iters = sum(res.loop_iters for res in jobs)
    if not iters:
        return None
    host = sum(res.loop_seconds - res.fetch_seconds for res in jobs)
    return 1e3 * host / iters
