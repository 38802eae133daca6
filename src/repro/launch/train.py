"""Production training launcher: mesh + FSDP×TP shardings + checkpoint.

On real hardware:   python -m repro.launch.train --arch granite-8b
On this CPU host:   python -m repro.launch.train --arch granite-8b \
                        --reduced --steps 20
(the full configs only *lower* here — use launch/dryrun.py for that).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.checkpoint import save_pytree
from repro.configs.base import get_config, reduced
from repro.data.synthetic import lm_batches
from repro.nn import model as M
from repro.nn import sharding as shd
from repro.optim import cosine_schedule, wsd_schedule
from repro.train.loop import make_train_step
from repro.utils import init_compile_cache


def main() -> None:
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant (CPU)")
    ap.add_argument("--schedule", choices=["cosine", "wsd"], default="cosine")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--mesh", choices=["none", "host"], default="none",
                    help="'host': build a mesh over all visible devices")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.schedule == "wsd":
        lr = wsd_schedule(args.lr, warmup=args.steps // 10,
                          stable=args.steps // 2, decay=args.steps // 3)
    else:
        lr = cosine_schedule(args.lr, warmup=args.steps // 10,
                             total=args.steps)

    params = M.init_params(jax.random.key(0), cfg)
    init_state, train_step = make_train_step(cfg, lr)
    state = init_state(params)

    if args.mesh == "host":
        n = len(jax.devices())
        mesh = jax.make_mesh((max(n // 4, 1), min(n, 4)), ("data", "model"))
        pspecs = shd.param_pspecs(params, cfg, mesh)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
        state = state._replace(
            params=jax.device_put(state.params, psh),
            opt=state.opt._replace(
                mu=jax.device_put(state.opt.mu, psh),
                nu=jax.device_put(state.opt.nu, psh)))

    step_fn = jax.jit(train_step, donate_argnums=0)
    data = lm_batches(cfg, args.batch, args.seq, seed=0)
    t0 = time.perf_counter()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        state, m = step_fn(state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:5d}  loss={float(m.loss):.4f}  "
                  f"ce={float(m.ce_loss):.4f}  lr={float(m.lr):.2e}  "
                  f"({time.perf_counter() - t0:.0f}s)", flush=True)
    if args.ckpt:
        save_pytree(state, args.ckpt)
        print("saved", args.ckpt)


if __name__ == "__main__":
    main()
