"""Compile a cell's programs at their real sizes for a described TPU v5e,
without a chip, and print what each needs of device memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <workload> [...]

For each cell: the engine's decode step (cache donated, as on the chip),
the chunked-prefill segment program at the longest bucket, its finalize
and the slot insert, with the weights and the live cache as arguments.
The kernels are forced to their compiled (not interpreted) mode inside
this process only, so the programs are the ones the chip runs. Nothing
executes: the numbers are the compiler's `memory_analysis()`.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

GB = 1e9


def rehearse(name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import weights
    from repro.kernels.decode_qattn import ops as dq_ops
    from repro.kernels.flash_prefill import ops as fp_ops
    from repro.nn import model as M

    dq_ops.resolve_interpret = lambda flag: False
    fp_ops.resolve_interpret = lambda flag: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    c = harness.load_cell(name)
    params = weights.abstract(c.config, sharding=one)
    eng = harness.engine(c, params)
    cfg, spec = eng.cfg, eng.spec
    lb = jnp.asarray(eng.layer_budgets, jnp.int32)
    cache = on_chip(jax.eval_shape(lambda: M.init_cache(
        cfg, spec, eng.slots, eng.prompt_len + eng.max_new,
        layer_budgets=lb, paged=eng.paged, block_len=eng.block_len,
        pool_blocks=eng.pool_blocks)))
    L = max(eng.buckets)
    st = on_chip(jax.eval_shape(lambda: M.init_prefill_state(cfg, L)))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    tok = jax.ShapeDtypeStruct((eng.slots, 1), jnp.int32, sharding=one)
    seg = jax.ShapeDtypeStruct((1, eng.chunk_len), jnp.int32, sharding=one)
    lbs = jax.ShapeDtypeStruct(lb.shape, jnp.int32, sharding=one)
    pc = on_chip(jax.eval_shape(eng._finalize.__wrapped__, st, lb,
                                jax.random.key(0)))
    progs = {
        "decode": (jax.jit(eng._decode.__wrapped__, donate_argnums=(1,)),
                   (params, cache, tok, key)),
        "chunk": (jax.jit(eng._chunk_step.__wrapped__, donate_argnums=(1,)),
                  (params, st, seg, i32)),
        "finalize": (jax.jit(eng._finalize.__wrapped__), (st, lbs, key)),
    }
    if eng.paged:
        ids = jax.ShapeDtypeStruct((eng.n_max_blocks,), jnp.int32,
                                   sharding=one)
        progs["insert"] = (jax.jit(eng._insert.__wrapped__,
                                   donate_argnums=(0,)),
                           (cache, pc, i32, ids, i32))
    else:
        progs["insert"] = (jax.jit(eng._insert.__wrapped__,
                                   donate_argnums=(0,)), (cache, pc, i32))
    from repro.utils import tree_bytes
    out = {"weights_GB": tree_bytes(params) / GB,
           "cache_GB": tree_bytes(cache) / GB,
           "scratch_GB": tree_bytes(st) / GB}
    for k, (fn, args) in progs.items():
        comp = fn.lower(*args).compile()
        ma = comp.memory_analysis()
        out[k] = dict(
            args_GB=ma.argument_size_in_bytes / GB,
            out_GB=ma.output_size_in_bytes / GB,
            temp_GB=ma.temp_size_in_bytes / GB,
            alias_GB=ma.alias_size_in_bytes / GB,
            kernel="tpu_custom_call" in comp.as_text())
    return out


def main() -> int:
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    for name in sys.argv[1:]:
        r = rehearse(name)
        print(name)
        for k, v in r.items():
            print(f"  {k}: {v}")
        d = r["decode"]
        live = d["args_GB"] + d["out_GB"] - d["alias_GB"] + d["temp_GB"]
        print(f"  decode step live: {live:.2f} GB (+ scratch "
              f"{r['scratch_GB']:.2f} GB while an admission streams)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
