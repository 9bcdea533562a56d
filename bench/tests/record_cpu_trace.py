#!/usr/bin/env python3
"""Record the small CPU trace that ``test_xtrace.py`` reads.

    JAX_PLATFORMS=cpu python3 bench/tests/record_cpu_trace.py

Inside a ``bench.trace_window`` span it runs a jitted program three times,
each inside a ``bench.decode_call`` span, with a ``bench.engine_step`` span
of host sleep between runs, and writes the ``.xplane.pb`` to
``data/cpu.xplane.pb``.
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.decode_call"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(src[0], os.path.join(HERE, "data", "cpu.xplane.pb"))
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
