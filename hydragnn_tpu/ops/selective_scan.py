"""The selective state-space scan (Mamba-1's recurrence) down a flat row array
made of contiguous runs, the state set to zero at every run's first row:

    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * u_t) (x) B_t     h [D, S], a run
    y_t = h_t C_t + skip * u_t

``u``, ``dt`` [N, D] (a row a token, ``D`` channels), ``B``, ``C`` [N, S],
``A`` [D, S] negative, ``skip`` [D]; ``node_graph`` [N] names each row's run (a
document of a packed flush; the padding rows are a run of their own). Held
whole the state history is ``[N, D, S]`` float32, 328 KB a token at D 5120,
S 16: neither route here makes it. The caller gates ``y`` itself (XLA fuses
the product into the gate's own matmul: docs/KERNELS.md has the timing).

``selective_scan`` is the ONE entry point and decides the route as
``models/token_attention.py`` ``segment_causal_attention`` does:

* on the TPU where no gradient is asked for (the engine's ``score_tokens``, an
  evaluation step): the Pallas kernel ``_scan_kernel``. A grid step holds a
  block of 1024 channels and a chunk of ``SCAN_CHUNK`` rows of ``u``, ``dt``
  and ``y`` as the arrays lie (a row a token); 8 rows at a time are folded in
  VMEM so that a row's 1024 channels are ONE ``[8, 128]`` vector register,
  the block's 16 states sixteen registers, carried through the chunk's loop
  and kept in VMEM scratch from chunk to chunk (the time axis is the
  sequential grid axis). ``B_t`` and ``C_t`` are scalars a state, read from
  SMEM and splat. A run's first row adds 1e30 to the ``dt`` the decay is
  taken of, so ``exp(dt A)`` is exactly 0 there (``A`` < 0) and the state
  starts from ``dt u B`` alone: a reset costs one scalar add a row, nothing a
  state. Everything is float32.
* elsewhere, and under a gradient: ``_scan_chunked``, the same recurrence in
  ``jax.numpy``: a ``lax.scan`` over chunks of ``CHUNK_ROWS`` rows that
  carries ``h`` [D, S], and inside a chunk the first-order recurrence as an
  associative scan over ``[chunk, D, S]``, rematerialized in the backward.
  Differentiable, so ``run_training`` trains a stack with such a layer.

Per state element and step the kernel spends two multiplications and an
``exp`` on the decay, a multiply-add on the input and one on the output: the
vector unit's work, with no matmul to hide behind. ``scan_chunks`` is what the
serving engine counts a flush's walk by. Device times: PERF.md (PR 45).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .segment import execution_platform

# Rows a grid step of the TPU kernel walks (the state stays in VMEM across
# them), and rows a chunk of the ``jax.numpy`` route holds as [chunk, D, S].
SCAN_CHUNK = 256
CHUNK_ROWS = 64
# Channels a grid step holds: one [8, 128] register a state.
SUBLANES, LANES = 8, 128
CHANNEL_BLOCK = SUBLANES * LANES
# Added to dt at a run's first row before the decay is taken: exp(-1e30 |A|)
# is exactly 0 in float32 for any |A| above 1e-28.
RESET = 1e30


def scan_chunks(rows: int) -> int:
    """Time chunks ONE call of the TPU kernel walks over ``rows`` rows (padded
    up to whole chunks), a channel block."""
    return -(-int(rows) // SCAN_CHUNK)


def run_starts(node_graph):
    """[N] bool: the rows at which a run of ``node_graph`` starts (row 0 is
    one)."""
    return jnp.concatenate(
        [jnp.ones((1,), bool), node_graph[1:] != node_graph[:-1]]
    )


# ------------------------------------------------------- the jax.numpy route
def _combine(left, right):
    """``h -> a h + b`` composed, ``left`` first."""
    a1, b1 = left
    a2, b2 = right
    return a1 * a2, a2 * b1 + b2


def _scan_chunked(u, dt, A, B, C, skip, first, chunk: int = CHUNK_ROWS):
    n, d = u.shape
    pad = -n % chunk
    if pad:
        u, dt, B, C = (jnp.pad(a, ((0, pad), (0, 0))) for a in (u, dt, B, C))
        first = jnp.pad(first, (0, pad))

    @jax.checkpoint
    def step(h, rows):
        u_c, dt_c, b_c, c_c, first_c = rows
        decay = jnp.exp(dt_c[:, :, None] * A[None])  # [chunk, D, S]
        decay = jnp.where(first_c[:, None, None], 0.0, decay)
        drive = (dt_c * u_c)[:, :, None] * b_c[:, None, :]
        a_cum, b_cum = jax.lax.associative_scan(_combine, (decay, drive))
        states = a_cum * h[None] + b_cum
        return states[-1], jnp.einsum("tds,ts->td", states, c_c)

    rows = tuple(
        a.reshape((-1, chunk) + a.shape[1:]) for a in (u, dt, B, C, first)
    )
    _, y = jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32), rows)
    return y.reshape(-1, d)[:n] + skip * u[:n]


# ------------------------------------------------------------ the TPU kernel
def _scan_kernel(
    b_ref, c_ref, big_ref,  # SMEM: [S, chunk], [S, chunk], [1, chunk]
    u_ref, dt_ref, a_ref, skip_ref, y_ref, h_ref,
    states: int, chunk: int,
):
    """One (channel block, time chunk) grid step. ``u_ref``, ``dt_ref`` and
    ``y_ref`` are [chunk, 1024] as the arrays lie (a row a token); 8 rows at
    a time are folded to [8, 8, 128] (a row's 1024 channels ONE register) for
    the recurrence and the 8 rows of ``y`` unfolded again for the store, both
    in VMEM."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = jnp.zeros(h_ref.shape, jnp.float32)

    a = [a_ref[s] for s in range(states)]

    def rows(i, h):
        r = pl.multiple_of(i * SUBLANES, SUBLANES)
        u_rows = u_ref[pl.ds(r, SUBLANES), :]
        u8 = u_rows.reshape(SUBLANES, SUBLANES, LANES)
        dt8 = dt_ref[pl.ds(r, SUBLANES), :].reshape(SUBLANES, SUBLANES, LANES)
        ys = []
        for k in range(SUBLANES):
            t = r + k
            dt = dt8[k]
            decay_of = dt + big_ref[0, t]
            drive = dt * u8[k]
            y, out = None, []
            for s in range(states):
                h_s = jnp.exp(decay_of * a[s]) * h[s] + drive * b_ref[s, t]
                y = h_s * c_ref[s, t] if y is None else y + h_s * c_ref[s, t]
                out.append(h_s)
            h = tuple(out)
            ys.append(y)
        y_ref[pl.ds(r, SUBLANES), :] = (
            jnp.stack(ys).reshape(SUBLANES, CHANNEL_BLOCK) + skip_ref[...] * u_rows
        )
        return h

    h = jax.lax.fori_loop(
        0, chunk // SUBLANES, rows, tuple(h_ref[s] for s in range(states))
    )
    for s in range(states):
        h_ref[s] = h[s]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def selective_scan_tpu(u, dt, A, B, C, skip, first, chunk: int = SCAN_CHUNK,
                       interpret: bool = False):
    """The kernel's call: ``u``, ``dt`` [N, D] float32, ``N`` whole chunks and ``D`` whole channel blocks of 1024 (the
    entry point pads both); ``first`` [N] bool. Returns [N, D]. Jitted, as
    ``block_range_attention`` is: a model's layers of one shape share ONE
    trace and ONE lowering of the kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = u.shape
    states = A.shape[1]
    blocks = d // CHANNEL_BLOCK
    rows = pl.BlockSpec((chunk, CHANNEL_BLOCK), lambda j, i: (i, j))
    scalars = pl.BlockSpec(
        (states, chunk), lambda j, i: (0, i), memory_space=pltpu.SMEM
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, states=states, chunk=chunk),
        grid=(blocks, n // chunk),
        in_specs=[
            scalars, scalars,
            pl.BlockSpec((1, chunk), lambda j, i: (0, i), memory_space=pltpu.SMEM),
            rows, rows,
            pl.BlockSpec((states, None, SUBLANES, LANES), lambda j, i: (0, j, 0, 0)),
            pl.BlockSpec((1, CHANNEL_BLOCK), lambda j, i: (0, j)),
        ],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((states, SUBLANES, LANES), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="selective_scan",
        interpret=interpret,
    )(
        B.T, C.T, jnp.where(first, RESET, 0.0).astype(jnp.float32)[None, :],
        u, dt, A.T.reshape(states, blocks, SUBLANES, LANES), skip[None, :],
    )


def _scan_padded_tpu(u, dt, A, B, C, skip, first):
    """``selective_scan_tpu`` over rows padded up to whole chunks (the padding
    a run of its own) and channels up to whole blocks (zeros: a channel of
    ``A`` 0 and ``u`` 0 stays 0)."""
    n, d = u.shape
    rows, cols = -n % SCAN_CHUNK, -d % CHANNEL_BLOCK
    if rows or cols:
        u, dt = (jnp.pad(a, ((0, rows), (0, cols))) for a in (u, dt))
        B, C = (jnp.pad(a, ((0, rows), (0, 0))) for a in (B, C))
        A, skip = jnp.pad(A, ((0, cols), (0, 0))), jnp.pad(skip, (0, cols))
        first = jnp.pad(first, (0, rows), constant_values=True)
    return selective_scan_tpu(u, dt, A, B, C, skip, first)[:n, :d]


@jax.custom_vjp
def _scan_tpu(u, dt, A, B, C, skip, first):
    """On the TPU: the kernel where the call is not differentiated, the
    ``jax.numpy`` route's forward and backward under a gradient (a backward
    kernel with the state recomputed a chunk is ROADMAP's to ask for)."""
    return _scan_padded_tpu(u, dt, A, B, C, skip, first)


def _scan_tpu_fwd(u, dt, A, B, C, skip, first):
    return jax.vjp(lambda *a: _scan_chunked(*a, first), u, dt, A, B, C, skip)


def _scan_tpu_bwd(vjp, g):
    return (*vjp(g), None)


_scan_tpu.defvjp(_scan_tpu_fwd, _scan_tpu_bwd)


def selective_scan(u, dt, A, B, C, skip, node_graph):
    """``y`` [N, D] of the recurrence at the top of this file over the runs of
    ``node_graph`` [N] (contiguous: collation), everything in float32. ``A``
    [D, S] is the NEGATIVE matrix itself (``-exp(A_log)``), ``dt`` what the
    softplus gave."""
    u, dt, B, C = (a.astype(jnp.float32) for a in (u, dt, B, C))
    first = run_starts(node_graph)
    if execution_platform() == "tpu":
        return _scan_tpu(u, dt, A, B, C, skip, first)
    return _scan_chunked(u, dt, A, B, C, skip, first)
