"""The one traffic generator. A mix is a data file, `traffic/<name>.json`,
of parameters; this module turns it into jobs.

A job is `n` requests submitted together. Its sizes come from the mix's
distributions at fixed quantiles, in an order fixed by the job's index,
so every seed serves the same requests' sizes in the same order: the
seed draws the token ids (and the weights). Runs with different seeds
therefore do the same work, scheduled alike; the jobs of one run differ
in their order.

Mix parameters:
  prompt  {"buckets": [...], <lengths>}  snapped up to the next bucket
          (capped at the largest)
  output  {<lengths>, "min": a, "max": b}  rounded and clipped
  tokens  "uniform": ids uniform over the vocabulary
  source  where the numbers come from (read by people, not by this code)

where <lengths> is either "median": m, "sigma": s (log-normal) or
"choices": [[length, weight], ...] (lengths in the shares of their
weights).
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Req:
    tokens: np.ndarray   # int32 prompt
    max_new: int


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _length(spec: dict, u: float) -> float:
    """The length at quantile `u` of a length distribution."""
    if "choices" in spec:
        total = sum(w for _, w in spec["choices"])
        acc = 0.0
        for x, w in spec["choices"]:
            acc += w / total
            if u < acc:
                return x
        return spec["choices"][-1][0]
    return spec["median"] * math.exp(spec["sigma"]
                                     * NormalDist().inv_cdf(u))


def _interleave(n: int) -> list:
    """A fixed permutation of range(n) that spreads neighbours apart
    (bit-reversal order), so long prompts are not paired with long
    outputs by construction."""
    bits = max(1, (n - 1).bit_length())
    keys = [int(format(i, f"0{bits}b")[::-1], 2) for i in range(n)]
    return sorted(range(n), key=lambda i: keys[i])


def sizes(mix: dict, n: int) -> list:
    """The job's (prompt, output) sizes at quantiles (i + 1/2) / n."""
    pm, om = mix["prompt"], mix["output"]
    buckets = sorted(pm["buckets"])
    us = [(i + 0.5) / n for i in range(n)]
    prompts = []
    for u in us:
        x = _length(pm, u)
        prompts.append(next((b for b in buckets if b >= x), buckets[-1]))
    outs = [int(min(om["max"], max(om["min"], round(_length(om, u)))))
            for u in us]
    order = _interleave(n)
    return [(prompts[i], outs[order[i]]) for i in range(n)]


def job(mix: dict, n: int, vocab: int, seed: int, index: int) -> list:
    """Job `index` of a run with `seed`: the fixed sizes in the order of
    job `index`, with token ids drawn from `seed`."""
    if mix.get("tokens", "uniform") != "uniform":
        raise ValueError(f"unknown token distribution {mix['tokens']!r}")
    rng = np.random.default_rng([int(seed) % 2**63, int(index)])
    sz = sizes(mix, n)
    order = np.random.default_rng([int(index), 1]).permutation(n)
    return [Req(rng.integers(0, vocab, size=sz[i][0], dtype=np.int64)
                .astype(np.int32), sz[i][1]) for i in order]


def warmup_job(mix: dict, vocab: int, seed: int, max_new: int = 3) -> list:
    """One request per prompt bucket: compiles every program the mix's
    requests use (each bucket's chunk and finalize programs, the insert,
    the decode step, block growth and slot reset)."""
    rng = np.random.default_rng([int(seed) % 2**63, 2**31])
    return [Req(rng.integers(0, vocab, size=b, dtype=np.int64)
                .astype(np.int32), max_new)
            for b in sorted(mix["prompt"]["buckets"])]
