"""How ``small_v5e.xplane.pb`` was recorded (on one TPU v5 lite chip, PR 22):

    python3 graftbench/testdata/record.py <out dir>

Three runs of one small jitted program under the program's own named scope,
each inside a ``device_step`` annotation, with a 20 ms ``host_pause``
annotation (a sleep) after each, all inside ``graftbench.window``. The
self-test ``graftbench/tests/test_trace_reduce.py`` checks the reduction
against what this script is known to have done.
"""

import sys
import time

import jax
import jax.numpy as jnp


@jax.jit
def step(x):
    with jax.named_scope("hydragnn.train_step"):
        for _ in range(4):
            x = jnp.tanh(x @ x) / 64.0
        return x


def main(out: str) -> None:
    x = jnp.ones((2048, 2048), jnp.float32)
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("graftbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("device_step"):
                step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("host_pause"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
