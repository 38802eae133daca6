"""setup_s: process start to the first timed job — weights made on the
device, programs compiled or loaded from the cache, the warm-up job."""


def read(ctx):
    return ctx.setup_s
