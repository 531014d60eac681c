#!/usr/bin/env python3
"""Compile a cell's serving programs for a described TPU v5e and print
their memory analysis, to size a cell's slots and pages before any chip
time is spent.

    JAX_PLATFORMS=cpu python3 bench/size.py --config qwen3-0.6b \
        --slots 4 --pool-tokens 27648 --max-context 8448

Compiles the fused decode step and the batched chunk-prefill program at
every row width the engine uses, with the kernels the chip would run
(the kernel dispatch is told it is on a TPU), and prints for each the
argument, output and temporary bytes.  A compile that passes here is not
a chip run: it gives no time, and it counts one program at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--pool-tokens", type=int, required=True)
    ap.add_argument("--max-context", type=int, required=True)
    ap.add_argument("--widths", default="",
                    help="comma-separated prefill row widths "
                         "(default: the engine's 1, 2, 4, ... slots)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    # steer the kernel dispatch as on the chip
    ops.on_tpu = lambda: True
    ops.kernel_interpret = lambda: False

    spec = model.load_config(args.config)
    cfg = model.model_config(spec)
    w = cfg.attn.window
    pages_per_slot = -(-args.max_context // w)
    n_pages = args.pool_tokens // w
    ecfg = model.engine_config(args.slots, pages_per_slot, n_pages)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    from repro.models import transformer as tfm
    from repro.serve.backends import mita as mb

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    params = shaped(jax.eval_shape(
        lambda k: model.init_params(spec, k), jax.random.PRNGKey(0)))
    run_cfg = model.backend_model_config(cfg, ecfg)
    states = shaped(jax.eval_shape(lambda: tfm.init_paged_states(
        run_cfg, ecfg.n_slots, ecfg.n_pages, ecfg.pages_per_slot)))
    s = ecfg.n_slots
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=dev)

    out = {"config": spec["name"], "slots": s, "pages_per_slot":
           pages_per_slot, "n_pages": n_pages,
           "params_bytes": sum(int(a.size) * a.dtype.itemsize
                               for a in jax.tree.leaves(params)),
           "state_bytes": sum(int(a.size) * a.dtype.itemsize
                              for a in jax.tree.leaves(states))}
    dec = mb._decode_fn(run_cfg, True, True)
    c = dec.lower(params, states, sd((s,), i32), sd((s,), i32),
                  sd((s,), i32), sd((s, pages_per_slot), i32), sd((s,), b),
                  sd((s,), i32), sd((s,), i32), sd((s,), f32),
                  jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
                  ).compile()
    out["decode"] = _mem(c)
    widths = ([int(x) for x in args.widths.split(",")] if args.widths
              else model.prefill_widths(s))
    pre = mb._batched_chunk_prefill_fn(run_cfg, ecfg.prefill_chunk,
                                       pages_per_slot)
    nc = ecfg.prefill_chunk
    for p in widths:
        c = pre.lower(params, states, sd((p, nc), i32), sd((p,), b),
                      sd((p, pages_per_slot), i32), sd((p,), i32),
                      sd((p,), i32), sd((p,), i32), sd((p,), i32)
                      ).compile()
        out[f"prefill_p{p}"] = _mem(c)
        out[f"prefill_p{p}"]["kernel"] = "tpu_custom_call" in c.as_text()
    print(json.dumps(out, indent=1))
    return 0


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes)}


if __name__ == "__main__":
    raise SystemExit(main())
