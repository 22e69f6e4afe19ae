"""``ops/selective_scan.py`` on the CPU at small sizes: the chunked
``jax.numpy`` route and the Pallas kernel (interpreted) against the recurrence
written one token a step in float64, with document starts placed at a chunk's
first row, at its last row and mid-chunk, a document of ONE row and a run of
padding rows at the end; rows and channels that are no whole chunk
or block; the gradient of the chunked route against finite differences; the
entry point's route by platform; the reset's constant. Values and counts,
never a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops import selective_scan as ss
from hydragnn_tpu.ops.segment import platform_override

D, S = 1024, 4  # one channel block of the kernel


def _one_token_a_step(u, dt, A, B, C, skip, node_graph):
    """The recurrence as written, float64, a Python loop: the state is zero
    before a run's first row."""
    u, dt, A, B, C, skip = (np.asarray(a, np.float64) for a in (u, dt, A, B, C, skip))
    h, y = np.zeros(A.shape), np.zeros(u.shape)
    for t in range(u.shape[0]):
        if t == 0 or node_graph[t] != node_graph[t - 1]:
            h[:] = 0.0
        h = np.exp(dt[t][:, None] * A) * h + (dt[t] * u[t])[:, None] * B[t][None, :]
        y[t] = h @ C[t] + skip * u[t]
    return y


def _inputs(rows, channels=D, states=S, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), channels))
    return dict(
        u=f32(rng.normal(size=(rows, channels))),
        dt=f32(dt0 * np.exp(rng.normal(size=(rows, channels)))),
        A=f32(-np.tile(np.arange(1.0, states + 1), (channels, 1))
              * np.exp(0.1 * rng.normal(size=(channels, states)))),
        B=f32(rng.normal(size=(rows, states))), C=f32(rng.normal(size=(rows, states))),
        skip=f32(1.0 + 0.05 * rng.normal(size=channels)),
    )


def _graph(rows, starts):
    """``node_graph`` [rows] whose runs start at 0 and at each of ``starts``."""
    ids = np.zeros(rows, np.int32)
    for s in starts:
        ids[s:] += 1
    return ids


# Runs placed against a chunk of 32 rows (the kernel's here; the chunked
# route's 16 divides it): a start ON a chunk's first row (32, 64), on a
# chunk's LAST row (31 and 95), mid-chunk (45), a run of ONE row (95..96),
# and the last run standing for the padding rows.
CHUNK, ROWS = 32, 128
STARTS = {
    "chunk's first row": [32, 64],
    "chunk's last row": [31, 95],
    "mid-chunk": [45],
    "every kind, a run of one row": [31, 32, 45, 64, 95, 96, 120],
    "one run": [],
}


@pytest.mark.parametrize("where", list(STARTS))
def pytest_both_routes_against_the_recurrence_a_token_a_step(where):
    x, graph = _inputs(ROWS), _graph(ROWS, STARTS[where])
    first = ss.run_starts(jnp.asarray(graph))
    assert int(first.sum()) == len(STARTS[where]) + 1
    want = _one_token_a_step(x["u"], x["dt"], x["A"], x["B"], x["C"], x["skip"], graph)
    scale = np.abs(want).max()
    args = tuple(x[k] for k in ("u", "dt", "A", "B", "C", "skip"))
    chunked = np.asarray(ss._scan_chunked(*args, first, chunk=16))
    assert np.abs(chunked - want).max() < 2e-6 * scale
    kernel = np.asarray(
        ss.selective_scan_tpu(*args, first, chunk=CHUNK, interpret=True)
    )
    assert np.abs(kernel - want).max() < 2e-6 * scale
    assert np.abs(kernel - chunked).max() < 2e-6 * scale  # the two routes against each other


def pytest_a_run_does_not_see_the_run_before_it():
    """Altering every row of the first run leaves the second run's output
    bit-equal, on both routes: no state crosses a boundary, at a chunk's edge
    or inside one."""
    x = _inputs(ROWS, seed=3)
    args = [x[k] for k in ("u", "dt", "A", "B", "C", "skip")]
    for start in (32, 45, 63):
        first = ss.run_starts(jnp.asarray(_graph(ROWS, [start])))
        other = list(args)
        other[0] = args[0].copy()
        other[0][:start] += 1.0
        other[1] = args[1].copy()
        other[1][:start] *= 2.0
        for route in (
            lambda a: ss._scan_chunked(*a, first, chunk=16),
            lambda a: ss.selective_scan_tpu(*a, first, chunk=CHUNK, interpret=True),
        ):
            base, moved = np.asarray(route(args)), np.asarray(route(other))
            assert np.array_equal(base[start:], moved[start:])
            assert not np.array_equal(base[:start], moved[:start])


def pytest_entry_point_pads_rows_and_channels_and_takes_the_route_by_platform(monkeypatch):
    """70 rows of 40 channels: no whole chunk, no whole block. On the CPU the
    entry point is the chunked route; told it runs on a TPU (the kernel
    interpreted) it pads to 256 rows, the padding a run of its own, and 1024
    channels, and gives the same numbers; under a gradient it is the chunked
    route's again."""
    x = _inputs(70, channels=40, seed=5)
    graph = _graph(70, [9, 33])
    args = tuple(x[k] for k in ("u", "dt", "A", "B", "C", "skip"))
    want = _one_token_a_step(*args, graph)
    scale = np.abs(want).max()
    got = np.asarray(ss.selective_scan(*args, jnp.asarray(graph)))
    assert got.shape == (70, 40) and np.abs(got - want).max() < 2e-6 * scale
    calls = []
    kernel = ss.selective_scan_tpu

    def interpreted(*a, **kw):
        calls.append(a[0].shape)
        return kernel(*a, interpret=True, **kw)

    monkeypatch.setattr(ss, "selective_scan_tpu", interpreted)
    with platform_override("tpu"):
        on_tpu = np.asarray(ss.selective_scan(*args, jnp.asarray(graph)))
        grad = jax.grad(
            lambda u: jnp.sum(ss.selective_scan(u, *args[1:], jnp.asarray(graph)) ** 2)
        )(jnp.asarray(x["u"]))
    assert calls == [(ss.SCAN_CHUNK, ss.CHANNEL_BLOCK)]  # the gradient called no kernel
    assert np.abs(on_tpu - want).max() < 2e-6 * scale
    here = jax.grad(
        lambda u: jnp.sum(ss.selective_scan(u, *args[1:], jnp.asarray(graph)) ** 2)
    )(jnp.asarray(x["u"]))
    assert np.allclose(np.asarray(grad), np.asarray(here), rtol=1e-5, atol=1e-6)


def pytest_chunked_route_is_differentiable_and_right():
    """The gradient of the chunked route in ``dt`` and ``B`` against central
    differences of the float64 recurrence, through a run boundary."""
    rows, channels = 24, 8
    x = _inputs(rows, channels=channels, seed=7)
    graph = _graph(rows, [10])
    first = ss.run_starts(jnp.asarray(graph))
    probe = np.random.default_rng(1).normal(size=(rows, channels))

    def loss(dt, B):
        y = ss._scan_chunked(x["u"], dt, x["A"], B, x["C"], x["skip"], first, chunk=8)
        return jnp.sum(y * probe)

    g_dt, g_b = jax.grad(loss, argnums=(0, 1))(jnp.asarray(x["dt"]), jnp.asarray(x["B"]))

    def exact(dt, B):
        return float(np.sum(
            _one_token_a_step(x["u"], dt, x["A"], B, x["C"], x["skip"], graph) * probe
        ))

    eps = 1e-6
    for t, c in ((3, 2), (9, 0), (10, 5), (20, 7)):
        up, down = x["dt"].astype(np.float64), x["dt"].astype(np.float64)
        up[t, c] += eps
        down[t, c] -= eps
        fd = (exact(up, x["B"]) - exact(down, x["B"])) / (2 * eps)
        assert abs(float(g_dt[t, c]) - fd) < 2e-3 * max(abs(fd), 1.0)
    for t, s in ((0, 1), (9, 3), (11, 0)):
        up, down = x["B"].astype(np.float64), x["B"].astype(np.float64)
        up[t, s] += eps
        down[t, s] -= eps
        fd = (exact(x["dt"], up) - exact(x["dt"], down)) / (2 * eps)
        assert abs(float(g_b[t, s]) - fd) < 2e-3 * max(abs(fd), 1.0)
    # Rows of the first run feel nothing of the second's loss alone.
    late = jax.grad(lambda dt: jnp.sum(
        ss._scan_chunked(x["u"], dt, x["A"], x["B"], x["C"], x["skip"], first, chunk=8)[10:]
    ))(jnp.asarray(x["dt"]))
    assert not np.asarray(late[:10]).any() and np.asarray(late[10:]).any()


def pytest_no_state_history_is_traced():
    """Neither route's jaxpr holds an ``[N, D, S]`` array: the chunked route's
    largest is a chunk's."""
    rows = 4 * ss.CHUNK_ROWS
    x = _inputs(rows, channels=64)
    first = ss.run_starts(jnp.zeros(rows, jnp.int32))
    args = tuple(x[k] for k in ("u", "dt", "A", "B", "C", "skip"))
    text = str(jax.make_jaxpr(lambda *a: ss._scan_chunked(*a, first))(*args))
    assert f"f32[{rows},64,{S}]" not in text
    assert f"f32[{ss.CHUNK_ROWS},64,{S}]" in text


def pytest_reset_constant_and_the_counts():
    # exp(-RESET |A|) is exactly 0 in float32 down to the smallest |A| a
    # seeded A_log can give, and RESET |A| does not overflow at the largest.
    for a in (1e-6, 1.0, 16.0, 1e6):
        assert float(jnp.exp(jnp.float32(ss.RESET) * jnp.float32(-a))) == 0.0
        assert np.isfinite(np.float32(ss.RESET) * np.float32(a))
    assert ss.scan_chunks(16896) == 66 and ss.scan_chunks(7680) == 30
    assert ss.scan_chunks(1) == 1 and ss.scan_chunks(257) == 2
    assert ss.CHANNEL_BLOCK == 1024 and 5120 % ss.CHANNEL_BLOCK == 0
    first = ss.run_starts(jnp.asarray([0, 0, 1, 1, 1, 2, 4, 4]))
    assert np.asarray(first).tolist() == [True, False, True, False, False, True, True, False]
