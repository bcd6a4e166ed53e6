"""Record ``tiny.xplane.pb``, the trace the reducer's tests read.

    python bench/tests/data/record_tiny_trace.py <out-dir>

On a TPU: three rounds of a jitted sort and a jitted matmul, each round
inside ``bench.dispatch`` and ``bench.fetch`` spans, all inside a
``bench.window`` span, with a short sleep between rounds.  The trace lands
under ``<out-dir>/plugins/profile/``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: (jnp.sort(x) * 2.0).sum())
    g = jax.jit(lambda a: a @ a)
    x = jnp.arange(1 << 16, dtype=jnp.float32)[::-1]
    a = jnp.ones((256, 256))
    f(x).block_until_ready()
    g(a).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
                z = g(a)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                float(y)
                z.block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
