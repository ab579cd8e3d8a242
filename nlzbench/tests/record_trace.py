#!/usr/bin/env python3
"""Record the small profiler trace the trace-reduction test reads.

    python3 nlzbench/tests/record_trace.py <out_dir>

Two annotated host spans ``nlzbench.op`` around two jitted programs, with
a host-side sleep between them so the trace holds a known idle gap.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    g = jax.jit(lambda x: (x * 2.0 + 1.0).max())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    jax.profiler.start_trace(out)
    for fn in (f, g):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("nlzbench.op"):
            fn(x).block_until_ready()
        print(f"mark {t!r}")
        time.sleep(0.05)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
