"""How testdata/small.xplane.pb was recorded, on the chip:

    python3 benchmark/testdata/record.py <output directory>

Four runs of a small jitted matrix product, each inside a `bench.step`
span, with a host sleep inside a `bench.nap` span after each, all inside
`bench.window`: a trace small enough to keep, whose idle share, busy
time and attribution of gaps the test works out by hand.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation('bench.window'):
        for _ in range(4):
            with jax.profiler.TraceAnnotation('bench.step'):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation('bench.nap'):
                time.sleep(0.002)
    jax.profiler.stop_trace()


if __name__ == '__main__':
    main(sys.argv[1])
