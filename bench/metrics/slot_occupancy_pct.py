"""slot_occupancy_pct: the scheduler's mean active-slot share per decode
step (`ContinuousGenerationResult.occupancy`), over the window's jobs
weighted by their decode steps."""


def read(ctx):
    steps = sum(res.decode_steps for _, res in ctx.jobs)
    if not steps:
        return None
    return 100.0 * sum(res.occupancy * res.decode_steps
                       for _, res in ctx.jobs) / steps
