#!/usr/bin/env python3
"""Compile a configuration's donated train step for a described TPU v5e
(no chip needed) and print its memory analysis.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py --config mamba2-370m --batch 8 --seq-len 1024

The compiler refuses here what it would refuse on the chip, including a
program that does not fit the device's memory.  The state's bytes are
those of the program's train state (bf16 params, float32 master, m, v).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--seq-len", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.common import harness, program

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = harness.read_json(harness.BENCH / "configs" / f"{args.config}.json")
    model = program.build(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    specs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        program.state_specs(model))
    batch = {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq_len),
                                            jnp.int32, sharding=one)}
    opt = harness.read_json(harness.BENCH / "traffic"
                            / "train-parity.json")["optimizer"]
    step = program.jit_train_step(model, program.train_config(opt))
    mem = step.lower(specs, batch).compile().memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(specs))
    print(json.dumps({
        "config": args.config, "batch": args.batch, "seq_len": args.seq_len,
        "state_bytes": state_bytes,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "generated_code_bytes": mem.generated_code_size_in_bytes,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
