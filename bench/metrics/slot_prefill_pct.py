"""slot_prefill_pct: share of slot-steps in which a slot was mid-admission
(PREFILLING: its prompt streaming in chunks, not yet decoding;
`ContinuousGenerationResult.prefill_share`, counted by the scheduler at
every decode step), over the window's jobs weighted by their decode
steps. None where the program does not count it."""


def read(ctx):
    jobs = [res for _, res in ctx.jobs]
    steps = sum(res.decode_steps for res in jobs)
    if not steps or not all(hasattr(res, "prefill_share") for res in jobs):
        return None
    return 100.0 * sum(res.prefill_share * res.decode_steps
                       for res in jobs) / steps
