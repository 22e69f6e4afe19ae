"""How ``scoped_v5e.xplane.pb`` was recorded (on one TPU v5 lite chip, PR 23):

    python3 graftbench/testdata/record_scoped.py <out dir>

Three runs of one small jitted train step under the program's own scope
vocabulary (``hydragnn_tpu/telemetry/scopes.py``), each inside a
``device_step`` annotation, all inside ``graftbench.window``. The step is the
gradient of: gather rows of a learned table ``embed`` by ``senders``
(``hydragnn.gather``; its backward is a scatter-add) -> Dense ``pre`` ->
``segment_sum`` over ``receivers`` (``hydragnn.agg.sum.xla``; its backward a
gather) -> Dense ``post``, in a flax module ``conv_0`` of a model ``Tiny``; a
squared-error loss (``hydragnn.loss``) and a plain SGD update
(``hydragnn.optimizer``). The self-test
``graftbench/tests/test_xplane_scopes.py`` checks the by-scope reduction
against what this script is known to have run.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import flax.linen as nn
import jax
import jax.numpy as jnp

from hydragnn_tpu.telemetry import scopes

N, E, F = 4096, 65536, 128


class Conv(nn.Module):
    @nn.compact
    def __call__(self, senders, receivers):
        x = self.param("embed", nn.initializers.normal(1.0), (N, F))
        with jax.named_scope(scopes.GATHER):
            x_j = x[senders]
        msg = nn.Dense(F, name="pre")(x_j)
        with scopes.agg_scope("sum", "xla"):
            agg = jax.ops.segment_sum(msg, receivers, num_segments=N)
        return nn.Dense(F, name="post")(agg)


class Tiny(nn.Module):
    @nn.compact
    def __call__(self, senders, receivers):
        return Conv(name="conv_0")(senders, receivers)


MODEL = Tiny()


@jax.jit
def step(params, senders, receivers):
    with jax.named_scope(scopes.TRAIN_STEP):
        def loss_fn(p):
            out = MODEL.apply(p, senders, receivers)
            with jax.named_scope(scopes.LOSS):
                return jnp.mean(jnp.square(out - 1.0))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope(scopes.OPTIMIZER):
            params = jax.tree_util.tree_map(
                lambda p, g: p - 1e-3 * g, params, grads
            )
        return params, loss


def main(out: str) -> None:
    key = jax.random.PRNGKey(0)
    senders = jax.random.randint(key, (E,), 0, N)
    receivers = jnp.sort(jax.random.randint(jax.random.fold_in(key, 1), (E,), 0, N))
    params = MODEL.init(key, senders, receivers)
    params, loss = step(params, senders, receivers)
    loss.block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("graftbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("device_step"):
                params, loss = step(params, senders, receivers)
                loss.block_until_ready()
    jax.profiler.stop_trace()
    print("recorded on", jax.devices()[0].device_kind, "loss", float(loss))


if __name__ == "__main__":
    main(sys.argv[1])
