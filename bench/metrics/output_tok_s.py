"""output_tok_s: every token served in the window's jobs over the
window's whole wall time (first job's call to the last job's return)."""


def read(ctx):
    return sum(r.n_tokens for _, r in ctx.served()) / ctx.window_s
