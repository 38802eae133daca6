"""How `correct` is decided: the served tokens against the plain
reference.

Once the window has closed, a sample of the requests it finished is
drawn from the seed, always with the longest served request in it, until
it holds `min_tokens` served tokens. Each sampled request is replayed
through the configuration's reference (`refs/<reference>.py`,
teacher-forced over its prompt and served tokens), and for every served
token the gap by which the reference's logit for it lies below the
reference's best logit at that position is read. The numbers over those
gaps (`numbers`: the widest gap, the mean gap) are compared against the
limits the cell's file gives. Greedy serving is exact when every gap is
0; rounding in the served precision lets near-ties flip, by gaps of the
size of that rounding.

`gaps(..., precision="fp8")` is the control: the same reference computed
one precision below the configuration's bfloat16 picks its own best token
at each position, and the gap of that token is read the same way.
"""
from __future__ import annotations

import os

import numpy as np


def sample(served: list, seed: int, min_tokens: int) -> list:
    """(request, result) pairs: the longest served one, then others in a
    seeded order until `min_tokens` served tokens are in."""
    ok = [(q, r) for q, r in served if r.n_tokens > 0]
    if not ok:
        return []
    longest = max(range(len(ok)), key=lambda i: (ok[i][1].n_tokens,
                                                 len(ok[i][0].tokens)))
    rng = np.random.default_rng([int(seed) % 2**63, 7])
    order = [longest] + [i for i in rng.permutation(len(ok))
                         if i != longest]
    out, n = [], 0
    for i in order:
        out.append(ok[i])
        n += ok[i][1].n_tokens
        if n >= min_tokens:
            break
    return out


def _reference(c):
    from harness import _module
    name = c.config["reference"]
    return _module(os.path.join(c.root, "bench", "refs", f"{name}.py"),
                   "ref_" + name)


def _kv(c) -> tuple:
    e = c.cell["engine"]
    if e["policy"] == "kivi2":
        return 2, e["window"]
    if e["policy"] == "full":
        return 16, 0
    raise ValueError(f"the reference has no cache model for policy "
                     f"{e['policy']!r}")


def gaps(c, params, pairs: list, precision: str = "f32") -> list:
    """Per sampled request, the gap of every served token (the reference
    computed at `precision`; under "fp8" the gap is that of the token the
    fp8 reference ranks first)."""
    import jax
    import jax.numpy as jnp
    ref = _reference(c)
    bits, group = _kv(c)
    max_out = c.mix["output"]["max"]
    block = group or 256
    m = c.config

    def f(prec):
        return jax.jit(lambda p, t, P, at: ref.served_logits(
            p, m, t, P, at, kv_bits=bits, group=group, precision=prec,
            block=block))

    f32 = f("f32")
    low = f("fp8") if precision != "f32" else None
    out = []
    with jax.default_matmul_precision("highest"):
        for q, r in pairs:
            P, N = len(q.tokens), r.n_tokens
            if group and P % group:
                raise ValueError(f"prompt of {P} is not a multiple of the "
                                 f"quantization group {group}")
            # one compiled shape per prompt bucket
            T = -(-(P + max_out - 1) // block) * block
            seq = np.zeros(T, np.int32)
            seq[:P] = q.tokens
            seq[P:P + N - 1] = r.tokens[:N - 1]
            at = np.full(max_out, P + N - 2, np.int32)
            at[:N] = np.arange(P - 1, P + N - 1)
            args = (params, jnp.asarray(seq), jnp.int32(P), jnp.asarray(at))
            lg = np.asarray(f32(*args))[:N]
            best = lg.max(-1)
            if low is None:
                pick = np.asarray(r.tokens[:N])
            else:
                pick = np.asarray(low(*args))[:N].argmax(-1)
            out.append(best - lg[np.arange(N), pick])
    return out


def numbers(g: list) -> dict:
    """The candidate numbers over a sample's gaps: the widest gap and the
    mean gap."""
    flat = np.concatenate(g) if g else np.full(1, np.inf)
    return {"max_logit_gap": float(flat.max()),
            "mean_logit_gap": float(flat.mean())}


def run(c, params, served: list, seed: int, precision: str = "f32") -> tuple:
    """(every candidate number with the tokens read, the numbers the cell
    compares — those its file gives a limit — each with its limit), for
    the served tokens (`precision` "f32") or the control ("fp8")."""
    ck = c.cell["check"]
    pairs = sample(served, seed, ck["min_tokens"])
    g = gaps(c, params, pairs, precision)
    got = numbers(g)
    return ({**got, "tokens": int(sum(len(x) for x in g))},
            {k: {"value": got[k], "limit": lim}
             for k, lim in ck["limits"].items()})
