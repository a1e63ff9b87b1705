#!/usr/bin/env python3
"""Record the small profiler trace ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>

On a TPU: inside a ``bench.window`` span, a ``bench.save`` span holding
two ``block_fp`` kernel calls on a 1 MiB float32 leaf, then a
``bench.train_step`` span holding a matrix product, then 20 ms in which
the host sleeps inside ``bench.idle_wait`` with the device idle.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from bench.common import program  # noqa: F401  (puts src on the path)
    from bench.common.trace import find_xplane
    from repro.kernels import block_fp

    x = jnp.arange(262144, dtype=jnp.float32)
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    mm = jax.jit(lambda m: m @ m)
    jax.block_until_ready(block_fp.block_fingerprint(x))
    jax.block_until_ready(mm(a))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.save"):
                for _ in range(2):
                    jax.block_until_ready(block_fp.block_fingerprint(x))
            with jax.profiler.TraceAnnotation("bench.train_step"):
                jax.block_until_ready(mm(a))
            with jax.profiler.TraceAnnotation("bench.idle_wait"):
                time.sleep(0.02)
        jax.profiler.stop_trace()
        shutil.copy(find_xplane(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
