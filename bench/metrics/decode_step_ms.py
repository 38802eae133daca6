"""decode_step_ms: device time of the engine's jitted decode program
(`jit__step` in the trace's "XLA Modules" line) over its runs."""
import tracing

PROGRAM = r"jit__step\b"


def read(ctx):
    if ctx.trace is None:
        return None
    t, n = tracing.summed_ns(ctx.trace["devices"][0]["modules"], PROGRAM,
                             *ctx.span)
    return t / n / 1e6 if n else None
