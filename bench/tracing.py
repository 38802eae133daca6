"""From a profiler trace to the numbers the per-layer metrics read.

`load` turns the `.xplane.pb` that `jax.profiler` writes into a small
plain structure (kept as JSON for the tests' recorded trace):

    {"devices": [{"name": plane, "ops": [[name, start_ns, dur_ns], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}

`ops` are the device's "XLA Ops" line (one event per HLO instruction,
named by the instruction alone — the trace's "%name = <HLO text>" is cut
at " = " — so Pallas kernels appear under the name of their jitted
wrapper, e.g. `decode_attn_paged_pallas.1`), `modules` its "XLA
Modules" line (one event per program run, e.g. `jit__step(<id>)`). Everything else the
reductions need is computed from that.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(log_dir: str) -> dict:
    """Read the newest `.xplane.pb` under `log_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[_short(e.name), e.start_ns, e.duration_ns]
                                for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events if e.duration_ns > 0)
    return out


def _short(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def options():
    """Profiler options for a traced run: device and runtime events, no
    Python function tracing (it slows the host loop being measured)."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


def span(tr: dict, name: str) -> tuple:
    """(start_ns, end_ns) of the host annotation `name` (the harness's
    window): the clock every reduction below clips to."""
    for n, s, d in tr["host"]:
        if n == name:
            return s, s + d
    raise KeyError(f"no host span {name!r} in the trace")


def _clip(evs, t0, t1):
    """[k, 2] start/end arrays of events clipped to [t0, t1]."""
    if not evs:
        return np.zeros((0, 2))
    a = np.array([[s, s + d] for _, s, d in evs], np.float64)
    a = np.clip(a, t0, t1)
    return a[a[:, 1] > a[:, 0]]


def merged(evs, t0, t1) -> np.ndarray:
    """Union of the events' intervals within [t0, t1], sorted [k, 2]."""
    a = _clip(evs, t0, t1)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0])]
    out = [a[0].copy()]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.array(out)


def busy_ns(dev: dict, t0, t1) -> float:
    m = merged(dev["ops"], t0, t1)
    return float((m[:, 1] - m[:, 0]).sum()) if len(m) else 0.0


def matching(evs, pattern: str, t0, t1) -> list:
    rx = re.compile(pattern)
    return [e for e in evs if rx.match(e[0]) and e[1] < t1
            and e[1] + e[2] > t0]


def summed_ns(evs, pattern: str, t0, t1) -> tuple:
    """(total duration, count) of events whose name matches `pattern`
    and that overlap [t0, t1]."""
    hit = matching(evs, pattern, t0, t1)
    return float(sum(d for _, _, d in hit)), len(hit)


def breakdown(tr: dict, t0, t1, top: int = 10) -> dict:
    """The device ops that took most time (summed per trace name), and
    the longest idle gaps of the first device, each labelled by the host
    event that overlaps it most (the harness's own window and job spans
    excluded)."""
    dev = tr["devices"][0]
    tot: dict = {}
    for n, s, d in matching(dev["ops"], "", t0, t1):
        tot[n] = tot.get(n, 0) + d
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    m = merged(dev["ops"], t0, t1)
    edges = np.concatenate([[t0], m.ravel() if len(m) else [], [t1]])
    gaps = edges.reshape(-1, 2)
    gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])][:top]
    host = [h for h in tr["host"]
            if not (h[0] == "window" or h[0].startswith("job"))]
    hs = np.array([h[1] for h in host], np.float64)
    he = hs + np.array([h[2] for h in host], np.float64)
    idle = []
    for g0, g1 in gaps:
        label = "none"
        if len(host):
            ov = np.minimum(he, g1) - np.maximum(hs, g0)
            i = int(np.argmax(ov))
            if ov[i] > 0:
                label = host[i][0]
        idle.append([label, (g1 - g0) / 1e9])
    return {"device_ops": [[n, d / 1e9] for n, d in ops],
            "idle_gaps": idle}
