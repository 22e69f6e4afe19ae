"""Pallas TPU kernel for fused segment aggregation — the hot op of PNA.

The reference's PNA conv (via PyG ``PNAConv``, /root/reference/hydragnn/models/
PNAStack.py:28-53) aggregates per-edge messages with four aggregators
(mean/min/max/std). Composed from XLA segment ops that is five scatter passes
over the [E, F] edge-message array (sum, count, sum-of-squares, min, max) —
and XLA's TPU scatter-add serializes updates instead of using the MXU.

This kernel turns the scatter into one-hot matmuls on the 128x128 MXU systolic
array: for a [BN]-node block and [BE]-edge block,

    onehot[n, e] = (receiver[e] == n)        # built in-register, exact in bf16
    sum   += onehot @ data                    # MXU
    count += rowsum(onehot)                   # VPU

TPU matmuls run bf16 multiplies by default (~0.4% relative error — the
bfloat16-first design point for TPU training). That is fine for sum/mean but
catastrophic for variance via E[x^2]-E[x]^2 (cancellation); so ``std`` is
computed with a SECOND fused pass over *centered* values,
var = mean((x - mean[ids])^2), which has no cancellation and keeps bf16-class
relative accuracy. Two passes over the edge data instead of five, with the
scatters on the MXU.

Measured on TPU v5e (E=16k, F=64, N=4k) on the ROUND-2 kernel: XLA
mean/min/max/std/count bundle ~88us; the fused path ~50us with min/max still
on XLA ``segment_max/min`` (elementwise extrema cannot ride the MXU). The
round-4 rework (f-packing + block-skip) did NOT hold that win on its first
hardware contact (TUNE_KERNEL_r05: 0.41-0.98x vs XLA, certification failing)
— hence the opt-in default; see pallas_enabled.

Those two scatters WERE the bottleneck once the sums had left theirs: at the
benchmark's PNA cell (32768 x 524288 x 256) ``agg.extrema.xla`` was 67.7 of a
180.9 ms train step and 43% of every evaluation step (PERF_LEDGER.jsonl, PR
26; PERF.md). Since PR 27 min and max over sorted receiver runs come from
ONE streamed pass with no scatter, a segmented scan that never leaves VMEM
(``_extrema_scan_kernel``): 3.6 ms where the two scatters took 20.3 at that
shape (PERF.md, PR 27). It needs no opt-in and is not behind HYDRAGNN_PALLAS:
``pna_aggregate`` takes it wherever the sorted arm has the batch's
``row_ptr`` and no edge-sharded axis, and XLA's scatters elsewhere. It is the
first Pallas code a benchmark cell runs.

The custom VJP keeps the backward on plain XLA gathers (gathers are fast on
TPU; only scatter is slow): for (sum, count) the data cotangent is
``d_sum[ids]``, and the stats bundle has an analytic scatter-free backward.
A side benefit of the centered formulation: the std value AND gradient are
~1000x more accurate than XLA's ``sqrt(relu(E[x²]−E[x]²)+eps)`` on
near-degenerate segments (values clustered around a large offset), where the
uncentered form cancels catastrophically in f32 (measured 6.6e-6 vs 5.8e-3
max grad error against an f64 reference).

On non-TPU backends the public entry points fall back to the masked XLA
segment ops in ``hydragnn_tpu.ops.segment`` (tests exercise the kernel via the
Pallas interpreter for exact parity with what compiles on TPU). Set
``HYDRAGNN_PALLAS=0`` to force the XLA path everywhere.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..telemetry import scopes
from . import segment as seg
from . import segment_sorted as srt

_BN = 128  # node-block rows (one MXU tile edge)
# Edge-block columns per grid step. Env-overridable (HYDRAGNN_PALLAS_BE) so
# benchmarks/tune_kernel.py can sweep block sizes on hardware without code
# edits; must be a multiple of 128 (lane count).
# A malformed value must not abort unrelated imports (code that never touches
# Pallas, or runs with HYDRAGNN_PALLAS=0): record the error here and raise it
# from _sum_count_pallas when the kernel is actually requested.
_BE_ERROR: Optional[str] = None
try:
    _BE = int(os.environ.get("HYDRAGNN_PALLAS_BE", "512"))
except ValueError:
    _BE, _BE_ERROR = 512, (
        "HYDRAGNN_PALLAS_BE must be an integer multiple of 128, got "
        f"{os.environ['HYDRAGNN_PALLAS_BE']!r}"
    )
if _BE_ERROR is None and (_BE <= 0 or _BE % 128 != 0):
    _BE, _BE_ERROR = 512, (
        f"HYDRAGNN_PALLAS_BE={_BE} must be a positive multiple of 128 (lanes)"
    )

# Platform gating lives in ops/segment.py (shared with segment_sorted's
# TPU-default gate — one source of truth, no circular import). Re-exported
# here under the names the trainer and tests have always used.
pallas_platform = seg.platform_override
_platform = seg.execution_platform


def pallas_enabled() -> bool:
    """True when the fused kernel should run. OPT-IN (HYDRAGNN_PALLAS=1)
    since round 5: the first on-hardware measurements of the reworked kernel
    (TPU v5e, 2026-07-31, TUNE_KERNEL_r05) showed it both failing its f64
    certification (ok=false at every swept block size) and slower than the
    XLA segment bundle (0.41-0.98x). The certification failure was
    root-caused (and fixed) later in r05: DEFAULT-precision MXU dots
    truncate f32 operands to bf16 on hardware only, so the std's
    single-pass sum-of-squares carried ~8e-3 error (16x the gate) and the
    un-rounded lo residual lost its low bits — see _stats_forward_pallas
    and _sum_count_pallas. Post-fix the kernel certifies ok=true ON
    HARDWARE at every block size (CERTIFY_r05.json, TUNE_KERNEL_r05.jsonl)
    with interpreter certification now hardware-faithful. It nevertheless
    STAYS opt-in: the end-to-end three-way race (BENCH_r05_*.json) was won
    by the scatter-free sorted path (ops/segment_sorted.py, the TPU
    default), with the kernel at ~parity with the XLA bundle. The kernel
    remains the candidate for workloads the sorted contract cannot cover
    (unsorted ids at scale); tests/test_pallas_tpu.py stays the hardware
    canary."""
    env = os.environ.get("HYDRAGNN_PALLAS")
    if env is not None:
        return env not in ("0", "false", "False")
    return False


def csr_kernel_enabled() -> bool:
    """Route Pallas traffic that carries precomputed CSR boundaries
    (``row_ptr`` — the PR-7 batch contract, graphs/csr.py) through the
    CSR-blocked kernel instead of the legacy one-hot scatter matmul. Rides
    UNDER the HYDRAGNN_PALLAS opt-in (pallas_enabled): with the kernel arm
    enabled, HYDRAGNN_PALLAS_CSR=0 forces the legacy one-hot kernel — the
    A/B pin benchmarks/pallas_matrix.py and tune_kernel.py use to race the
    two kernel generations on hardware. Default on: when a caller has CSR
    boundaries the run-walk kernel does strictly less work (no id compares,
    exact empty-block skip from the pointers)."""
    return pallas_enabled() and os.environ.get(
        "HYDRAGNN_PALLAS_CSR", "1"
    ) not in ("0", "false", "False")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _round_bf16(v: jnp.ndarray) -> jnp.ndarray:
    """Round f32 to the nearest bf16-representable f32 via integer bit math.

    NOT ``v.astype(bfloat16).astype(float32)``: XLA:TPU runs with excess
    precision allowed and folds that f32->bf16->f32 convert pair to the
    IDENTITY, which silently turned the hi/lo accuracy split into hi = x,
    lo = 0 — the kernel ran single-pass bf16 on hardware (measured r05:
    split=True output bit-identical to split=False, ~5e-2 error) while the
    interpreter, which does not fold the pair, certified ~1e-4. Bit masking
    can't be folded. Round-half-up: adding 0x8000 before masking carries
    into the exponent exactly when rounding up to the next binade should.
    Finite inputs only (NaN payloads may change; we never feed NaN/inf)."""
    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    u = (u + jnp.uint32(0x8000)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _wants_split(dtype) -> bool:
    """Single source of the hi/lo accuracy-split policy: the split only buys
    accuracy when the input has more mantissa bits than bf16 — for bf16
    activations (mixed precision) lo == 0 and the extra pass is pure waste."""
    return dtype != jnp.bfloat16


def _sum_count_kernel(ids_ref, data_ref, sum_ref, cnt_ref):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    base = pl.program_id(0) * _BN
    rows = jax.lax.broadcasted_iota(jnp.int32, (_BN, _BE), 0) + base
    ids = ids_ref[:]  # (1, BE); padded/masked edges carry id -1 → no row matches
    onehot = (rows == ids).astype(jnp.float32)  # (BN, BE)
    sum_ref[:] += jnp.dot(onehot, data_ref[:], preferred_element_type=jnp.float32)
    cnt_ref[:] += jnp.sum(onehot, axis=1, keepdims=True)


def _sum_count_split_kernel(ids_ref, hi_ref, lo_ref, sum_ref, cnt_ref):
    """Accuracy variant: the TPU MXU multiplies in bf16, but the one-hot factor
    is exact in bf16, so splitting data into a bf16 hi/lo pair and doing two
    matmuls recovers ~f32 accuracy at 2x the MXU work (the bf16x2 trick; XLA's
    HIGH precision would spend 3 passes because it must also split the one-hot
    operand, which for us is exact)."""
    import jax.experimental.pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    base = pl.program_id(0) * _BN
    rows = jax.lax.broadcasted_iota(jnp.int32, (_BN, _BE), 0) + base
    onehot = (rows == ids_ref[:]).astype(jnp.float32)
    sum_ref[:] += jnp.dot(
        onehot, hi_ref[:], preferred_element_type=jnp.float32
    ) + jnp.dot(onehot, lo_ref[:], preferred_element_type=jnp.float32)
    cnt_ref[:] += jnp.sum(onehot, axis=1, keepdims=True)


def pallas_skip_enabled() -> bool:
    """Block-skip variant (HYDRAGNN_PALLAS_SKIP=1): collation packs graphs
    contiguously, so each edge block's receivers span a narrow node window and
    most (node-block, edge-block) grid pairs provably cannot interact. The
    variant scalar-prefetches per-edge-block receiver ranges, predicates the
    one-hot matmul away for non-overlapping pairs (pl.when), and clamps the
    skipped pairs' DMA index to block 0 so revisited blocks do not re-fetch —
    on a diagonal-ish pattern this cuts both MXU work and HBM traffic by
    ~E_blocks/overlap. Default OFF: its one paired on-chip figure is 1.005x
    the base kernel (2026-07-31, TPU v5 lite). chip_smoke.py compiles and
    certifies it on the chip; benchmarks/tune_kernel.py can sweep it via the
    env.

    Read at TRACE time: like HYDRAGNN_PALLAS / HYDRAGNN_PALLAS_BE, this flag
    must be set before the process traces its first step — a later env toggle
    does not affect already-cached traces under jit."""
    return os.environ.get("HYDRAGNN_PALLAS_SKIP", "0") not in ("0", "false", "False")


def _block_overlap(i, j, lo_ref, hi_ref):
    """Can edge block j's receivers touch node block i? ONE definition shared
    by the skip kernel's compute predicate and the DMA index maps — if these
    ever diverged, a pair the index map clamps to block 0 could still compute,
    silently accumulating the wrong edge data."""
    base = i * _BN
    return (hi_ref[j] >= base) & (lo_ref[j] < base + _BN)


def _skip_kernel():
    """Block-skip twin of _sum_count_kernel/_sum_count_split_kernel (any
    operand count): same accumulation math, guarded by the prefetched
    receiver-range overlap test."""
    import jax.experimental.pallas as pl

    def kern(lo_ref, hi_ref, ids_ref, *args):
        ops, sum_ref, cnt_ref = args[:-2], args[-2], args[-1]
        i = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            sum_ref[:] = jnp.zeros_like(sum_ref)
            cnt_ref[:] = jnp.zeros_like(cnt_ref)

        base = i * _BN

        @pl.when(_block_overlap(i, j, lo_ref, hi_ref))
        def _():
            rows = jax.lax.broadcasted_iota(jnp.int32, (_BN, _BE), 0) + base
            onehot = (rows == ids_ref[:]).astype(jnp.float32)
            acc = jnp.dot(onehot, ops[0][:], preferred_element_type=jnp.float32)
            for op in ops[1:]:
                acc = acc + jnp.dot(
                    onehot, op[:], preferred_element_type=jnp.float32
                )
            sum_ref[:] += acc
            cnt_ref[:] += jnp.sum(onehot, axis=1, keepdims=True)

    return kern


def _sum_count_pallas(
    data: jnp.ndarray,
    ids: jnp.ndarray,
    num_segments: int,
    interpret: bool,
    split: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    import jax.experimental.pallas as pl

    if _BE_ERROR is not None:
        raise ValueError(_BE_ERROR)
    e, f = data.shape
    e_pad = _round_up(max(e, _BE), _BE)
    n_pad = _round_up(max(num_segments, _BN), _BN)
    ids_p = jnp.full((1, e_pad), -1, jnp.int32).at[0, :e].set(ids.astype(jnp.int32))

    data32 = data.astype(jnp.float32)
    # f-packing: at f <= 64 the hi/lo pair fits side-by-side in one 128-lane
    # tile (hi in lanes [0:f], lo lane-aligned at [64:64+f]), so the accuracy
    # split costs ZERO extra MXU work — the un-packed split path pays 2x. The
    # one-hot factor is shared, so one matmul yields both column groups and the
    # final hi+lo add happens in f32 outside the kernel.
    packed = split and 2 * f <= 128
    # hi and lo are rounded to bf16 HERE (via _round_bf16 — bit math the
    # compiler cannot fold; see its docstring for the excess-precision trap
    # that silently zeroed lo on hardware), not left for the MXU: a
    # DEFAULT-precision dot truncates f32 operands to bf16 on hardware but
    # not in interpreter mode. With every operand bf16-representable the
    # hardware dot is EXACT (one-hot x bf16 products), so interpreter and
    # TPU now compute the same split to ~accumulation order.
    if packed:
        f_pad = 128
        hi = _round_bf16(data32)
        lo = _round_bf16(data32 - hi)
        data_p = (
            jnp.zeros((e_pad, f_pad), jnp.float32)
            .at[:e, :f].set(hi)
            .at[:e, 64 : 64 + f].set(lo)
        )
        operands = (data_p,)
        kernel = _sum_count_kernel
    else:
        f_pad = _round_up(max(f, 128), 128)
        data_p = jnp.zeros((e_pad, f_pad), jnp.float32).at[:e, :f].set(data32)
        if split:
            hi = _round_bf16(data_p)
            lo = _round_bf16(data_p - hi)
            operands = (hi, lo)
            kernel = _sum_count_split_kernel
        else:
            operands = (data_p,)
            kernel = _sum_count_kernel

    grid = (n_pad // _BN, e_pad // _BE)
    edge_spec = pl.BlockSpec((_BE, f_pad), lambda i, j: (j, 0))
    out_specs = [
        pl.BlockSpec((_BN, f_pad), lambda i, j: (i, 0)),
        pl.BlockSpec((_BN, 1), lambda i, j: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
    ]
    ids_spec = pl.BlockSpec((1, _BE), lambda i, j: (0, j))
    if pallas_skip_enabled():
        from jax.experimental.pallas import tpu as pltpu

        nblk_e = e_pad // _BE
        blk = ids_p[0].reshape(nblk_e, _BE)
        valid = blk >= 0
        lo = jnp.where(valid, blk, jnp.int32(2147483647)).min(axis=1)
        hi = jnp.where(valid, blk, jnp.int32(-1)).max(axis=1)

        def _edge_idx(i, j, lo_ref, hi_ref):
            # Skipped pairs re-address block 0: an unchanged block index means
            # the pipeline skips the DMA, so skipped iterations cost no HBM.
            return (jnp.where(_block_overlap(i, j, lo_ref, hi_ref), j, 0), 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, _BE),
                    lambda i, j, lo_ref, hi_ref: (
                        0,
                        _edge_idx(i, j, lo_ref, hi_ref)[0],
                    ),
                )
            ]
            + [pl.BlockSpec((_BE, f_pad), _edge_idx)] * len(operands),
            out_specs=[
                pl.BlockSpec((_BN, f_pad), lambda i, j, lo_ref, hi_ref: (i, 0)),
                pl.BlockSpec((_BN, 1), lambda i, j, lo_ref, hi_ref: (i, 0)),
            ],
        )
        out_sum, out_cnt = pl.pallas_call(
            _skip_kernel(),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(lo, hi, ids_p, *operands)
    else:
        out_sum, out_cnt = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[ids_spec] + [edge_spec] * len(operands),
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(ids_p, *operands)
    total = out_sum[:num_segments, :f]
    if packed:
        total = total + out_sum[:num_segments, 64 : 64 + f]
    return total, out_cnt[:num_segments, 0]


# ----------------------------------------------------------- CSR-blocked kernel
def _csr_kernel():
    """CSR run-walk twin of the one-hot kernels (any operand count): the
    one-hot factor is built from ROW POINTERS, not id comparisons —
    ``onehot[n, e] = row_start[n] <= e_global < row_end[n]`` — so the kernel
    never loads the edge-id array at all, and contiguous receiver runs give
    an EXACT empty-block skip (the scalar-prefetched per-node-block edge
    ranges come straight from ``row_ptr``, no id scan to derive them)."""
    import jax.experimental.pallas as pl

    def kern(lo_ref, hi_ref, rs_ref, re_ref, *args):
        ops, sum_ref, cnt_ref = args[:-2], args[-2], args[-1]
        i = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            sum_ref[:] = jnp.zeros_like(sum_ref)
            cnt_ref[:] = jnp.zeros_like(cnt_ref)

        @pl.when((j >= lo_ref[i]) & (j <= hi_ref[i]))
        def _():
            cols = jax.lax.broadcasted_iota(jnp.int32, (_BN, _BE), 1) + j * _BE
            # rs/re blocks are (BN, 1): broadcast against the (BN, BE) iota.
            onehot = ((cols >= rs_ref[:]) & (cols < re_ref[:])).astype(
                jnp.float32
            )
            acc = jnp.dot(onehot, ops[0][:], preferred_element_type=jnp.float32)
            for op in ops[1:]:
                acc = acc + jnp.dot(
                    onehot, op[:], preferred_element_type=jnp.float32
                )
            sum_ref[:] += acc
            cnt_ref[:] += jnp.sum(onehot, axis=1, keepdims=True)

    return kern


def _csr_sum_count_pallas(
    data: jnp.ndarray,
    row_ptr: jnp.ndarray,
    num_segments: int,
    interpret: bool,
    split: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused (sum, count) over contiguous receiver runs given by ``row_ptr``
    [num_segments + 1] (the CSR batch contract). Masked rows must arrive
    pre-zeroed with their edges owned by padding segments — exactly the
    collation contract the sorted prefix path already relies on. Same
    hi/lo bf16x2 accuracy split and f-packing as the one-hot kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if _BE_ERROR is not None:
        raise ValueError(_BE_ERROR)
    e, f = data.shape
    e_pad = _round_up(max(e, _BE), _BE)
    n_pad = _round_up(max(num_segments, _BN), _BN)
    rp = row_ptr.astype(jnp.int32)
    # Rows beyond num_segments own no edges: empty runs [e, e).
    row_start = jnp.full((n_pad, 1), e, jnp.int32).at[:num_segments, 0].set(
        rp[:-1]
    )
    row_end = jnp.full((n_pad, 1), e, jnp.int32).at[:num_segments, 0].set(
        rp[1:]
    )

    data32 = data.astype(jnp.float32)
    packed = split and 2 * f <= 128
    if packed:
        f_pad = 128
        hi = _round_bf16(data32)
        lo = _round_bf16(data32 - hi)
        data_p = (
            jnp.zeros((e_pad, f_pad), jnp.float32)
            .at[:e, :f].set(hi)
            .at[:e, 64 : 64 + f].set(lo)
        )
        operands = (data_p,)
    else:
        f_pad = _round_up(max(f, 128), 128)
        data_p = jnp.zeros((e_pad, f_pad), jnp.float32).at[:e, :f].set(data32)
        if split:
            hi = _round_bf16(data_p)
            lo = _round_bf16(data_p - hi)
            operands = (hi, lo)
        else:
            operands = (data_p,)

    # Per-node-block edge-block ranges, straight from the pointers: block i's
    # edges live in [row_ptr[i*BN], row_ptr[min((i+1)*BN, N)]) — contiguous
    # by the CSR contract. hi_blk = -1 marks an empty block (predicate and
    # DMA clamp both fail j <= hi).
    n_blocks = n_pad // _BN
    lo_edge = row_start.reshape(n_blocks, _BN).min(axis=1)
    hi_edge = row_end.reshape(n_blocks, _BN).max(axis=1)  # exclusive
    nonempty = hi_edge > lo_edge
    lo_blk = jnp.where(nonempty, lo_edge // _BE, 0).astype(jnp.int32)
    hi_blk = jnp.where(
        nonempty, (jnp.maximum(hi_edge, 1) - 1) // _BE, -1
    ).astype(jnp.int32)

    def _edge_idx(i, j, lo_ref, hi_ref):
        # Skipped pairs re-address block 0: an unchanged block index means
        # the pipeline skips the DMA (same trick as the skip kernel).
        return (jnp.where((j >= lo_ref[i]) & (j <= hi_ref[i]), j, 0), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks, e_pad // _BE),
        in_specs=[
            pl.BlockSpec((_BN, 1), lambda i, j, lo_ref, hi_ref: (i, 0)),
            pl.BlockSpec((_BN, 1), lambda i, j, lo_ref, hi_ref: (i, 0)),
        ]
        + [pl.BlockSpec((_BE, f_pad), _edge_idx)] * len(operands),
        out_specs=[
            pl.BlockSpec((_BN, f_pad), lambda i, j, lo_ref, hi_ref: (i, 0)),
            pl.BlockSpec((_BN, 1), lambda i, j, lo_ref, hi_ref: (i, 0)),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
    ]
    out_sum, out_cnt = pl.pallas_call(
        _csr_kernel(),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(lo_blk, hi_blk, row_start, row_end, *operands)
    total = out_sum[:num_segments, :f]
    if packed:
        total = total + out_sum[:num_segments, 64 : 64 + f]
    return total, out_cnt[:num_segments, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _csr_sum_count_vjp(data, row_ptr, ids, num_segments, interpret, split, dtype_name):
    return _csr_sum_count_pallas(data, row_ptr, num_segments, interpret, split)


def _csr_sum_count_fwd(data, row_ptr, ids, num_segments, interpret, split, dtype_name):
    out = _csr_sum_count_pallas(data, row_ptr, num_segments, interpret, split)
    return out, (row_ptr, ids)


def _csr_sum_count_bwd(num_segments, interpret, split, dtype_name, res, cots):
    row_ptr, ids = res
    d_sum, d_cnt = cots
    del d_cnt  # count has no data dependence
    # CSR contract: data arrives pre-zeroed at masked rows, ids RAW (masked
    # rows target padding segments) — masking composes through the caller's
    # jnp.where, so the backward is a plain gather like the sorted path's.
    idx = jnp.clip(ids.astype(jnp.int32), 0, num_segments - 1)
    d_data = jnp.take(d_sum, idx, axis=0)
    return (
        d_data.astype(dtype_name),
        jnp.zeros(row_ptr.shape, jax.dtypes.float0),
        jnp.zeros(ids.shape, jax.dtypes.float0),
    )


_csr_sum_count_vjp.defvjp(_csr_sum_count_fwd, _csr_sum_count_bwd)


def csr_segment_sum_count(
    data, row_ptr, ids, num_segments: int, interpret: bool = False,
    split: bool = True,
):
    """Fused (sum, count) per segment over precomputed CSR boundaries — the
    run-walk kernel behind every conv family's CSR-path aggregation
    (sum/mean for SAGE/GIN/CGCNN, sum+count for MFC, both passes of the PNA
    stats bundle). ``ids`` is only consumed by the gather backward; the
    forward walks ``row_ptr`` alone."""
    return _csr_sum_count_vjp(
        data, row_ptr, ids, num_segments, interpret, split, str(data.dtype)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _sum_count_vjp(data, ids, num_segments, interpret, split, dtype_name):
    return _sum_count_pallas(data, ids, num_segments, interpret, split)


def _sum_count_fwd(data, ids, num_segments, interpret, split, dtype_name):
    out = _sum_count_pallas(data, ids, num_segments, interpret, split)
    return out, ids


def _sum_count_bwd(num_segments, interpret, split, dtype_name, ids, cots):
    d_sum, d_cnt = cots
    del d_cnt  # count has no data dependence
    valid = (ids >= 0)[:, None]
    idx = jnp.clip(ids, 0, num_segments - 1)
    d_data = jnp.where(valid, d_sum[idx], 0.0)
    return d_data.astype(dtype_name), jnp.zeros(ids.shape, jax.dtypes.float0)


_sum_count_vjp.defvjp(_sum_count_fwd, _sum_count_bwd)


def segment_sum_count(
    data, ids, num_segments: int, interpret: bool = False, split: bool = True
):
    """Fused (sum, count) per segment via one-hot MXU matmuls.

    ``ids`` < 0 marks masked/padding rows (excluded from both outputs).
    ``data``: [E, F] float; ``ids``: [E] int. Returns ``(sum [N,F], count [N])``.
    ``split=True`` uses the bf16 hi/lo trick for ~f32 accuracy — free when
    f <= 64 (hi/lo pack side-by-side into one 128-lane tile and share the
    one-hot matmul), two matmuls otherwise; ``split=False`` is single-pass
    bf16 — use it ONLY for data that is already bf16-representable: on
    hardware the MXU truncates f32 operands to bf16 regardless of
    cancellation structure (~2^-9 relative error; skipping the split on the
    "no cancellation" argument for sums of squares is exactly what failed
    the r05 on-chip certification at 16x the gate).
    Differentiable w.r.t. ``data`` (gather backward).

    The primal dtype rides as a STATIC argument — a zero-size carrier array in
    the residuals (the previous design) picks up an inconsistent sharding
    under ``shard_map`` and breaks the graph-parallel backward.
    """
    return _sum_count_vjp(
        data, ids, num_segments, interpret, split, str(data.dtype)
    )


def _stats_forward(
    data, ids, num_segments, eps, axis_name, interpret, want_std,
    sorted_route=False, row_ptr=None,
):
    if sorted_route:
        # Scatter-free path: data arrives pre-zeroed at masked rows and ids
        # RAW (sorted; masked rows target padding segments). The centered
        # second pass needs no mask handling — masked rows have data 0 and
        # a ~0 padding-segment mean, and padding outputs are never consumed.
        # With CSR boundaries (row_ptr) the segment bounds are precomputed
        # at collation — zero searchsorted calls in the traced step.
        total, count = srt.segment_sum_count_auto(
            data, ids, num_segments, row_ptr=row_ptr
        )
        if axis_name is not None:
            total = jax.lax.psum(total, axis_name)
            count = jax.lax.psum(count, axis_name)
        safe = jnp.maximum(count, 1.0)[:, None]
        mean = total / safe
        if not want_std:
            return total, mean, jnp.zeros_like(mean), count
        idx = jnp.clip(ids, 0, num_segments - 1)
        # sumsq via a CENTERED XLA scatter, not the prefix path: squares are
        # tiny exactly where 1/std^2 amplifies error (near-degenerate
        # segments), and prefix-difference noise (~1e-5 abs) there costs
        # ~5e-3 in the std GRADIENT — 8x worse than even XLA's uncentered
        # formula at some shapes. The centered scatter has no cancellation
        # (~1e-6 fwd, ~1e-5 grad, same as the Pallas arm). Masked rows are
        # exactly zero here (data pre-zeroed, padding-segment mean is 0), so
        # no mask argument is needed. Net: 4 of 5 scatters still eliminated;
        # only PNA's std pass keeps one.
        sumsq = jax.ops.segment_sum(
            jnp.square(data - mean[idx]), ids, num_segments=num_segments
        )
        if axis_name is not None:
            sumsq = jax.lax.psum(sumsq, axis_name)
        # Single-element segments have sumsq == 0 identically; pin them to
        # sqrt(eps) (the bwd already treats their dstd as 0).
        std = jnp.where(
            count[:, None] > 1.0,
            jnp.sqrt(sumsq / safe + eps),
            jnp.full_like(mean, jnp.sqrt(eps)),
        )
        return total, mean, std, count
    return _stats_forward_pallas(
        data, ids, num_segments, eps, axis_name, interpret, want_std,
        row_ptr=row_ptr,
    )


def _stats_forward_pallas(data, ids, num_segments, eps, axis_name, interpret,
                          want_std, row_ptr=None):
    def _sum_count(d, i):
        # CSR route (row_ptr present under the HYDRAGNN_PALLAS opt-in): the
        # run-walk kernel — raw sorted ids, data pre-zeroed at masked rows
        # (the caller enforced the CSR contract before dispatching here).
        if row_ptr is not None:
            return csr_segment_sum_count(
                d, row_ptr, i, num_segments, interpret,
                _wants_split(data.dtype),
            )
        return segment_sum_count(
            d, i, num_segments, interpret, _wants_split(data.dtype)
        )

    total, count = _sum_count(data, ids)
    if axis_name is not None:
        total = jax.lax.psum(total, axis_name)
        count = jax.lax.psum(count, axis_name)
    safe = jnp.maximum(count, 1.0)[:, None]
    mean = total / safe
    if not want_std:
        return total, mean, jnp.zeros_like(mean), count
    # Centered second pass. This MUST take the hi/lo accuracy split: on the
    # real MXU a DEFAULT-precision f32 dot truncates its operands to bf16
    # (jax/_src/pallas/mosaic/lowering.py precision handling), capping each
    # square at ~2^-9 relative error — ~8e-3 absolute on the std at certify
    # magnitudes, 15x over the 5e-4 gate. This single-pass shortcut (the
    # "squares don't cancel" argument missed operand truncation) is what
    # failed the r05 on-hardware certification at every block size while the
    # interpreter (true-f32 dots) passed. With the split the simulated-MXU
    # std error is ~1.4e-5; at f <= 64 the packed layout makes it free.
    idx = jnp.clip(ids, 0, num_segments - 1)
    centered = jnp.where((ids >= 0)[:, None], data - mean[idx], 0.0)
    if row_ptr is not None:
        sumsq, _ = csr_segment_sum_count(
            jnp.square(centered), row_ptr, ids, num_segments, interpret, True
        )
    else:
        sumsq, _ = segment_sum_count(
            jnp.square(centered), ids, num_segments, interpret, True
        )
    if axis_name is not None:
        sumsq = jax.lax.psum(sumsq, axis_name)
    std = jnp.sqrt(sumsq / safe + eps)
    return total, mean, std, count


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _stats(data, ids, num_segments, eps, axis_name, interpret, want_std,
           sorted_route=False, row_ptr=None):
    return _stats_forward(
        data, ids, num_segments, eps, axis_name, interpret, want_std,
        sorted_route, row_ptr,
    )


def _stats_fwd(data, ids, num_segments, eps, axis_name, interpret, want_std,
               sorted_route=False, row_ptr=None):
    out = _stats_forward(
        data, ids, num_segments, eps, axis_name, interpret, want_std,
        sorted_route, row_ptr,
    )
    total, mean, std, count = out
    return out, (data, ids, mean, std, count, row_ptr)


def _stats_bwd(num_segments, eps, axis_name, interpret, want_std, sorted_route,
               res, cots):
    """Analytic scatter-free backward. With s=Σx, μ=s/n, σ=sqrt(Σ(x-μ)²/n+eps):
    since Σ_e (x_e - μ) = 0 exactly, the μ-coupling inside σ vanishes and

        dx_e = ds̄[i] + dμ̄[i]/n[i] + dσ̄[i]·(x_e − μ[i])/(σ[i]·n[i]),  i=id(e)

    — pure gathers, no scatter (scatter is the slow op on TPU). Under graph
    parallelism the incoming cotangents are per-device shares of the global
    outputs, so they are psum'd first (VJP of the forward psum)."""
    data, ids, mean, std, count, row_ptr = res
    d_total, d_mean, d_std, d_count = cots
    del d_count  # no data dependence
    if axis_name is not None:
        d_total = jax.lax.psum(d_total, axis_name)
        d_mean = jax.lax.psum(d_mean, axis_name)
        d_std = jax.lax.psum(d_std, axis_name)
    safe = jnp.maximum(count, 1.0)[:, None]
    per_seg_lin = d_total + d_mean / safe  # [N, F]
    valid = (ids >= 0)[:, None]
    idx = jnp.clip(ids, 0, num_segments - 1)
    d_data = per_seg_lin[idx]
    if want_std:
        # Single-element segments have x ≡ μ, so dσ/dx is identically 0; guard
        # the 1/σ=1/sqrt(eps) amplification against residual rounding in x−μ.
        per_seg_quad = jnp.where(count[:, None] > 1.0, d_std / (std * safe), 0.0)
        d_data = d_data + per_seg_quad[idx] * (data - mean[idx])
    d_data = jnp.where(valid, d_data, 0.0)
    d_row_ptr = (
        None if row_ptr is None
        else jnp.zeros(row_ptr.shape, jax.dtypes.float0)
    )
    return (
        d_data.astype(data.dtype),
        jnp.zeros(ids.shape, jax.dtypes.float0),
        d_row_ptr,
    )


_stats.defvjp(_stats_fwd, _stats_bwd)


def fused_segment_stats(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    eps: float = 1e-5,
    axis_name: Optional[str] = None,
    interpret: Optional[bool] = None,
    want_std: bool = True,
    sorted_ids: bool = False,
    row_ptr: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(sum, mean, std, count) per segment from two fused passes — the PNA
    sum/mean/std aggregator family (drop-in for segment_sum + segment_mean +
    segment_std + segment_count), with an analytic scatter-free backward.
    ``want_std=False`` skips the centered second pass (std comes back as
    zeros) when only the sum/mean family is needed.

    ``row_ptr`` (the CSR batch contract, graphs/csr.py) supplies precomputed
    segment boundaries: the sorted prefix path then runs zero searchsorted
    calls, and under HYDRAGNN_PALLAS the CSR run-walk kernel replaces the
    one-hot scatter matmul for both fused passes.

    Under edge-sharded graph parallelism (``axis_name``) the raw partial sums
    are psum'd across the shard axis before the mean/std are formed — the same
    cross-device composition as the scatter path, but two collectives total.
    Per-shard edge slices keep the sorted order but NOT the global ``row_ptr``
    offsets, so the boundaries are re-derived locally in that mode.
    """
    ids = segment_ids.astype(jnp.int32)
    if interpret is None:
        interpret = _platform() != "tpu"
    use_sorted, use_csr_kernel, row_ptr = _sorted_route(
        sorted_ids, row_ptr, axis_name, num_local_edges=segment_ids.shape[0]
    )
    # Neither route taken, this bundle runs the one-hot kernel whatever
    # pallas_enabled() says: pna_aggregate asks first.
    arm = _arm(use_sorted, use_csr_kernel, row_ptr, otherwise="pallas")
    with scopes.agg_scope("stats", arm):
        if use_sorted or use_csr_kernel:
            # Sorted/CSR contract: zero masked rows, keep RAW (sorted) ids —
            # a -1 marker would break the non-decreasing order the path
            # requires.
            srt.attach_layout_check(ids)
            if mask is not None:
                data = jnp.where(mask[:, None], data, 0)
            return _stats(
                data.astype(jnp.float32), ids, num_segments, eps, axis_name,
                interpret, want_std, use_sorted, row_ptr,
            )
        if mask is not None:
            ids = jnp.where(mask, ids, -1)
        return _stats(
            data, ids, num_segments, eps, axis_name, interpret, want_std,
            False, None,
        )


# ------------------------------------------- extrema over sorted receiver runs
# Edge rows a grid step streams through VMEM, and rows of them scanned in
# registers at a time ([_XC, 128] of min, of max and of ids are 2 vregs each).
_XB = 512
_XC = 16


def _extrema_scan_kernel(
    ids_ref, data_ref, mn_ref, mx_ref, idt_ref, cid_ref, cmn_ref, cmx_ref
):
    """One block of the inclusive SEGMENTED (min, max) scan down the rows.

    ids are non-decreasing, so ``ids[i - s] == ids[i]`` says rows ``i - s .. i``
    are one run: ``log2(_XC)`` shift-compare-select steps scan a chunk in
    registers (a shift that wraps round the chunk combines rows of ONE run
    only, so the run's last row still ends up with the run's extrema and
    nothing else is read), then the chunk joins the ``(id, min, max)`` row
    carried from the chunk before it, through the fori_loop inside a block and
    through scratch from block to block (the grid is sequential). The last
    row of a run holds the run's min and max; a run of any length, the
    padding node's included, costs what its rows cost."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f = data_ref.shape[1]
    lane = cid_ref.shape[1]
    slabs = [(c0, min(lane, f - c0)) for c0 in range(0, f, lane)]

    @pl.when(pl.program_id(0) == 0)
    def _():
        cid_ref[...] = jnp.full(cid_ref.shape, -1, jnp.int32)  # no id is < 0
        cmn_ref[...] = jnp.zeros(cmn_ref.shape, jnp.float32)
        cmx_ref[...] = jnp.zeros(cmx_ref.shape, jnp.float32)

    # The ids arrive lane-major (a [1, _XB] row: no padded [E, 1] copy in HBM)
    # and are wanted down the sublanes, the same id in every lane.
    idt_ref[...] = jnp.broadcast_to(ids_ref[...], (128, _XB)).T

    def chunk(c, carry):
        cid, cmns, cmxs = carry
        r0 = pl.multiple_of(c * _XC, _XC)
        ids = idt_ref[pl.ds(r0, _XC), :][:, :lane]
        steps = []
        s = 1
        while s < _XC:
            steps.append((s, pltpu.roll(ids, s, 0) == ids))
            s *= 2
        joined = ids == cid
        last_mn, last_mx = [], []
        for (c0, w), cmn, cmx in zip(slabs, cmns, cmxs):
            mn = mx = data_ref[pl.ds(r0, _XC), c0:c0 + w].astype(jnp.float32)
            for s, same in steps:
                same = same[:, :w]
                mn = jnp.where(same, jnp.minimum(mn, pltpu.roll(mn, s, 0)), mn)
                mx = jnp.where(same, jnp.maximum(mx, pltpu.roll(mx, s, 0)), mx)
            mn = jnp.where(joined[:, :w], jnp.minimum(mn, cmn), mn)
            mx = jnp.where(joined[:, :w], jnp.maximum(mx, cmx), mx)
            mn_ref[pl.ds(r0, _XC), c0:c0 + w] = mn.astype(mn_ref.dtype)
            mx_ref[pl.ds(r0, _XC), c0:c0 + w] = mx.astype(mx_ref.dtype)
            last_mn.append(mn[_XC - 1:, :])
            last_mx.append(mx[_XC - 1:, :])
        return ids[_XC - 1:, :], tuple(last_mn), tuple(last_mx)

    # The carried rows stay one array a 128-lane slab: Mosaic refuses a lane
    # slice of a loop-carried [1, f] value.
    cid, cmns, cmxs = jax.lax.fori_loop(
        0, _XB // _XC, chunk,
        (
            cid_ref[...],
            tuple(cmn_ref[:, c0:c0 + w] for c0, w in slabs),
            tuple(cmx_ref[:, c0:c0 + w] for c0, w in slabs),
        ),
    )
    cid_ref[...] = cid
    for (c0, w), cmn, cmx in zip(slabs, cmns, cmxs):
        cmn_ref[:, c0:c0 + w] = cmn
        cmx_ref[:, c0:c0 + w] = cmx


def _extrema_csr(data, ids, row_ptr, num_segments: int, interpret: bool):
    """(min, max) of each receiver's contiguous run of ``data`` rows from ONE
    streamed pass and no scatter: the scan kernel above, then the row at
    ``row_ptr[n + 1] - 1`` of each output for node ``n`` (one N-row gather
    each), 0 where the run is empty. Min and max do not round, so this is
    bit-equal to ``jax.ops.segment_min`` / ``segment_max`` on every run."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, f = data.shape
    if e == 0:
        zeros = jnp.zeros((num_segments, f), data.dtype)
        return zeros, zeros
    e_pad = _round_up(e, _XB)
    if e_pad != e:
        # Rows past the end form a run of their own that no node points into.
        data = jnp.pad(data, ((0, e_pad - e), (0, 0)))
        ids = jnp.pad(ids, (0, e_pad - e), constant_values=num_segments)
    lane = min(f, 128)
    rows = pl.BlockSpec((_XB, f), lambda j: (j, 0))
    scanned_mn, scanned_mx = pl.pallas_call(
        _extrema_scan_kernel,
        grid=(e_pad // _XB,),
        in_specs=[pl.BlockSpec((1, _XB), lambda j: (0, j)), rows],
        out_specs=[rows, rows],
        out_shape=[jax.ShapeDtypeStruct((e_pad, f), data.dtype)] * 2,
        scratch_shapes=[
            pltpu.VMEM((_XB, 128), jnp.int32),
            pltpu.VMEM((1, lane), jnp.int32),
            pltpu.VMEM((1, f), jnp.float32),
            pltpu.VMEM((1, f), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(ids.reshape(1, e_pad), data)
    row_ptr = row_ptr.astype(jnp.int32)
    last = jnp.maximum(row_ptr[1:] - 1, 0)
    filled = (row_ptr[1:] > row_ptr[:-1])[:, None]
    return (
        jnp.where(filled, jnp.take(scanned_mn, last, axis=0), 0),
        jnp.where(filled, jnp.take(scanned_mx, last, axis=0), 0),
    )


def _extrema_arm(row_ptr) -> str:
    return "xla" if row_ptr is None else "pallas_csr"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def segment_extrema(
    data, ids, num_segments: int, axis_name: Optional[str] = None, row_ptr=None
):
    """(min, max) per segment with a gather-based backward: the cotangent flows
    to every row equal to its segment's extremum (the standard subgradient),
    avoiding XLA's scatter-heavy segment_min/max VJP on TPU. Empty segments
    yield 0.

    With ``row_ptr`` (the CSR batch contract: ``ids`` RAW and non-decreasing,
    masked rows in the padding segments' runs, whose outputs nobody reads, and
    no ``axis_name``) the forward is :func:`_extrema_csr`, one streamed pass
    and no scatter. Without it ``ids`` < 0 marks masked rows and the forward
    is XLA's two scatters. The backward is the same either way."""
    # This IS the custom_vjp, so the scope is opened inside it and again in
    # its backward: JAX traces both when it pleases, under the caller's name
    # stack, and a scope round the call alone would be written twice wherever
    # the forward is traced after that scope has closed (the scan step).
    with scopes.agg_scope("extrema", _extrema_arm(row_ptr)):
        if row_ptr is not None:
            srt.attach_layout_check(ids)
            return _extrema_csr(
                data, ids, row_ptr, num_segments, _platform() != "tpu"
            )
        mask = ids >= 0
        safe_ids = jnp.where(mask, ids, 0)
        mn = seg.segment_min(data, safe_ids, num_segments, mask=mask, axis_name=axis_name)
        mx = seg.segment_max(data, safe_ids, num_segments, mask=mask, axis_name=axis_name)
        return mn, mx


def _extrema_fwd(data, ids, num_segments, axis_name, row_ptr=None):
    mn, mx = segment_extrema(data, ids, num_segments, axis_name, row_ptr)
    return (mn, mx), (data, ids, mn, mx, row_ptr)


def _extrema_bwd(num_segments, axis_name, res, cots):
    data, ids, mn, mx, row_ptr = res
    d_mn, d_mx = cots
    with scopes.agg_scope("extrema", _extrema_arm(row_ptr)):
        if axis_name is not None:
            d_mn = jax.lax.psum(d_mn, axis_name)
            d_mx = jax.lax.psum(d_mx, axis_name)
        valid = (ids >= 0)[:, None]
        idx = jnp.clip(ids, 0, num_segments - 1)
        d_data = jnp.where(valid & (data == mn[idx]), d_mn[idx], 0.0) + jnp.where(
            valid & (data == mx[idx]), d_mx[idx], 0.0
        )
        return (
            d_data.astype(data.dtype),
            jnp.zeros(ids.shape, jax.dtypes.float0),
            None if row_ptr is None
            else jnp.zeros(row_ptr.shape, jax.dtypes.float0),
        )


segment_extrema.defvjp(_extrema_fwd, _extrema_bwd)


def certify_pallas(
    e: int = 16384,
    f: int = 64,
    n: int = 4096,
    reps: int = 20,
    seed: int = 0,
    contiguous: bool = False,
    sorted_arm: bool = True,
    csr_arm: bool = True,
) -> dict:
    """On-device certification of the fused kernel against the XLA segment
    ops: forward + gradient parity on the PNA aggregation workload (reference
    shape: /root/reference/hydragnn/models/PNAStack.py:28-53) and measured
    speedup of the compiled sum/mean/std bundle. Run by bench.py on every
    benchmark invocation and by tests/test_pallas_tpu.py on TPU.

    Errors are measured against an f64 numpy ground truth (comparing fused to
    XLA directly would mis-attribute XLA's own E[x²]−E[x]² cancellation error
    in the std gradient to the kernel). Returns {backend, max_err_fwd,
    max_err_grad, xla_err_fwd, xla_err_grad, speedup, pallas_ms, xla_ms}.
    Uses whatever platform pallas gating currently resolves to (pin with
    ``pallas_platform`` / HYDRAGNN_PALLAS as needed).

    ``contiguous=True`` SORTS the segment ids — the production pattern
    (collation packs graphs contiguously, so receivers ascend across the edge
    array). This is the shape on which the block-skip variant
    (HYDRAGNN_PALLAS_SKIP) can skip work; with uniformly random ids every
    edge block spans all nodes and nothing is skippable, so a skip-vs-base
    comparison on random ids is structurally meaningless.
    """
    import time

    import numpy as np

    def _problem(e_, f_, n_, seed_):
        key = jax.random.PRNGKey(seed_)
        k1, k2, k3 = jax.random.split(key, 3)
        data = jax.random.normal(k1, (e_, f_), jnp.float32) * 2.0 + 1.0
        ids = jax.random.randint(k2, (e_,), 0, n_)
        if contiguous:
            ids = jnp.sort(ids)
        mask = jax.random.uniform(k3, (e_,)) > 0.1
        return data, ids, mask

    def _bundles(ids, mask, n_):
        def fused_bundle(d):
            return fused_segment_stats(d, ids, n_, mask=mask)

        def xla_bundle(d):
            safe = jnp.where(mask, ids, 0)
            return (
                seg.segment_sum(d, safe, n_, mask=mask),
                seg.segment_mean(d, safe, n_, mask=mask),
                seg.segment_std(d, safe, n_, mask=mask),
                seg.segment_count(safe, n_, mask=mask),
            )

        def scalarize(bundle):
            def fn(d):
                total, mean, std, count = bundle(d)
                # All three differentiable outputs contribute to the cotangent.
                return jnp.sum(total * 0.3 + mean * 1.7 - std * 0.9)

            return fn

        return fused_bundle, xla_bundle, scalarize

    def _accuracy(data, ids, mask, n_):
        """(fused fwd/grad err, xla fwd/grad err) vs an f64 host ground truth."""
        e_, f_ = data.shape
        fused_bundle, xla_bundle, scalarize = _bundles(ids, mask, n_)
        f_fused = jax.jit(fused_bundle)
        f_xla = jax.jit(xla_bundle)
        g_fused = jax.jit(jax.grad(scalarize(fused_bundle)))
        g_xla = jax.jit(jax.grad(scalarize(xla_bundle)))

        d64 = np.asarray(data, np.float64)
        ids_h = np.asarray(ids)
        mask_h = np.asarray(mask)
        total64 = np.zeros((n_, f_))
        count64 = np.zeros(n_)
        np.add.at(total64, ids_h[mask_h], d64[mask_h])
        np.add.at(count64, ids_h[mask_h], 1.0)
        safe64 = np.maximum(count64, 1.0)[:, None]
        mean64 = total64 / safe64
        centered = np.where(mask_h[:, None], d64 - mean64[ids_h], 0.0)
        sumsq64 = np.zeros((n_, f_))
        np.add.at(sumsq64, ids_h[mask_h], np.square(centered)[mask_h])
        std64 = np.sqrt(sumsq64 / safe64 + 1e-5)
        # grad of S = Σ 0.3·total + 1.7·mean − 0.9·std w.r.t. data:
        per_seg = 0.3 + 1.7 / safe64
        grad64 = np.where(
            mask_h[:, None], np.broadcast_to(per_seg[ids_h], (e_, f_)), 0.0
        )
        quad = np.where(count64[:, None] > 1.0, -0.9 / (std64 * safe64), 0.0)
        grad64 += np.where(mask_h[:, None], quad[ids_h] * centered, 0.0)
        truth = (total64, mean64, std64, count64)

        def errs(outs, grad):
            # Per-output decomposition (kept in the artifact): the r05
            # hardware failure was only diagnosable once the max was split
            # into components (raw-sum error implicated the matmul itself).
            comp = {
                name: float(np.max(np.abs(np.asarray(o, np.float64) - t)))
                for name, o, t in zip(
                    ("total", "mean", "std", "count"), outs, truth
                )
            }
            grad_err = float(
                np.max(np.abs(np.asarray(grad, np.float64) - grad64))
            )
            return max(comp.values()), grad_err, comp

        fused_errs = errs(
            jax.block_until_ready(f_fused(data)), jax.block_until_ready(g_fused(data))
        )
        xla_errs = errs(
            jax.block_until_ready(f_xla(data)), jax.block_until_ready(g_xla(data))
        )
        return fused_errs, xla_errs

    # Certification must measure the KERNEL even now that the production
    # default is the XLA path (fused_* gates on pallas_enabled, which would
    # otherwise compare XLA to itself). Force-enable for the duration.
    _saved_env = os.environ.get("HYDRAGNN_PALLAS")
    os.environ["HYDRAGNN_PALLAS"] = "1"
    try:
        data, ids, mask = _problem(e, f, n, seed)
        (
            (max_err_fwd, max_err_grad, err_components),
            (xla_err_fwd, xla_err_grad, xla_components),
        ) = _accuracy(data, ids, mask, n)
        # The split=True kernel forks on the packing boundary (2f <= 128 packs
        # hi/lo into one tile; wider shapes run the two-matmul kernel). Certify
        # BOTH sides: the flagship f (packed when <= 64) above, and a wide shape
        # exercising _sum_count_split_kernel here — production takes that path
        # whenever hidden_dim > 64.
        f_wide = max(2 * f, 96)
        wide = _problem(e // 4, f_wide, max(n // 4, _BN), seed + 1)
        (wide_err_fwd, wide_err_grad, _), _ = _accuracy(*wide, max(n // 4, _BN))

        fused_bundle, xla_bundle, _ = _bundles(ids, mask, n)
        f_fused = jax.jit(fused_bundle)
        f_xla = jax.jit(xla_bundle)

        def best_ms(fn):
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(data))
                times.append(time.perf_counter() - t0)
            return 1000.0 * min(times)

        pallas_ms = best_ms(f_fused)
        xla_ms = best_ms(f_xla)

        # Further arms on contiguous ids: the scatter-free sorted path
        # (ops/segment_sorted.py) and the CSR run-walk kernel
        # (csr_segment_sum_count — the row_ptr batch contract). Measured
        # UNMASKED — certify's random mask violates the sorted contract
        # (masked rows must target padding segments), so their accuracy is
        # checked against their own f64 truth. Forward AND gradient, like
        # the other arms.
        # Shared tolerance gate (precision/tolerance.py): the same fwd/grad
        # bounds every consumer of "within tolerance" uses.
        from ..precision.tolerance import KERNEL_CERT_GATE as _gate

        sorted_res = None
        if contiguous and (sorted_arm or csr_arm):
            d64 = np.asarray(data, np.float64)
            ids_h = np.asarray(ids)
            tot64 = np.zeros((n, f))
            np.add.at(tot64, ids_h, d64)
            cnt64 = np.bincount(ids_h, minlength=n).astype(np.float64)
            safe64 = np.maximum(cnt64, 1.0)[:, None]
            mean64 = tot64 / safe64
            sq64 = np.zeros((n, f))
            np.add.at(sq64, ids_h, np.square(d64 - mean64[ids_h]))
            std64 = np.sqrt(sq64 / safe64 + 1e-5)
            truths = (tot64, mean64, std64, cnt64)
            # Same cotangent as the other arms' scalarize; dstd at
            # single-count segments is identically 0 (std pinned there).
            per_lin = 0.3 + 1.7 / safe64
            quad = np.where(
                cnt64[:, None] > 1.0, -0.9 / (std64 * safe64), 0.0
            )
            g64 = per_lin[ids_h] + quad[ids_h] * (d64 - mean64[ids_h])
            row_ptr = jnp.asarray(
                np.searchsorted(ids_h, np.arange(n + 1)).astype(np.int32)
            )

            def _measure_arm(tag, env, row_ptr_arg):
                saved = {k: os.environ.get(k) for k in env}
                os.environ.update(env)
                try:
                    def bundle(d):
                        return fused_segment_stats(
                            d, ids, n, sorted_ids=True, row_ptr=row_ptr_arg
                        )

                    f_arm = jax.jit(bundle)

                    def _scalar(d):
                        total, mean, std, _ = bundle(d)
                        return jnp.sum(total * 0.3 + mean * 1.7 - std * 0.9)

                    g_arm = jax.jit(jax.grad(_scalar))
                    outs = jax.block_until_ready(f_arm(data))
                    grad = jax.block_until_ready(g_arm(data))
                    err = max(
                        float(np.max(np.abs(np.asarray(o, np.float64) - t)))
                        for o, t in zip(outs, truths)
                    )
                    err_grad = float(
                        np.max(np.abs(np.asarray(grad, np.float64) - g64))
                    )
                    arm_ms = best_ms(f_arm)
                    return err, err_grad, arm_ms
                finally:
                    for k, v in saved.items():
                        if v is None:
                            os.environ.pop(k, None)
                        else:
                            os.environ[k] = v

            sorted_res = {}
            if sorted_arm:
                err, err_grad, sorted_ms = _measure_arm(
                    "sorted", {"HYDRAGNN_SEGMENT_SORTED": "1"}, None
                )
                # Gradient gate: no regression vs the INCUMBENT default (the
                # XLA bundle) rather than the kernel-grade 5e-4 — the sorted
                # std grad inherits ~1/std^2 amplification at near-degenerate
                # segments from its ~1e-5 sumsq noise (measured ~5e-3), while
                # the XLA path production trains on today carries ~9e-2 from
                # its E[x^2]-E[x]^2 cancellation. Promotion must not lose
                # accuracy; it need not beat the Pallas kernel's.
                sorted_res.update(
                    sorted_ms=round(sorted_ms, 4),
                    sorted_err_fwd=err,
                    sorted_err_grad=err_grad,
                    sorted_ok=err < _gate.fwd
                    and err_grad <= max(_gate.fwd, xla_err_grad),
                    sorted_speedup_vs_xla=round(
                        sorted_ms and xla_ms / sorted_ms, 3
                    ),
                )
            if csr_arm:
                # CSR kernel arm: HYDRAGNN_PALLAS is already forced on for
                # the whole certification; pin the sorted prefix path OFF so
                # the row_ptr route resolves to the run-walk kernel, not the
                # prefix-sum arm (the TPU default).
                err, err_grad, csr_ms = _measure_arm(
                    "csr",
                    {
                        "HYDRAGNN_SEGMENT_SORTED": "0",
                        "HYDRAGNN_PALLAS_CSR": "1",
                    },
                    row_ptr,
                )
                # Same gates as the one-hot kernel (KERNEL_CERT_GATE): the
                # CSR kernel shares its bf16x2 split and analytic backward,
                # so kernel-grade 5e-4 fwd / 5e-3 grad apply unchanged.
                sorted_res.update(
                    csr_ms=round(csr_ms, 4),
                    csr_err_fwd=err,
                    csr_err_grad=err_grad,
                    csr_ok=_gate.check(err, err_grad)["ok"],
                    csr_speedup_vs_xla=round(csr_ms and xla_ms / csr_ms, 3),
                )
    finally:
        if _saved_env is None:
            os.environ.pop("HYDRAGNN_PALLAS", None)
        else:
            os.environ["HYDRAGNN_PALLAS"] = _saved_env
    # Single source of truth for the certification tolerances is now the
    # SHARED gate in precision/tolerance.py (KERNEL_CERT_GATE) — one
    # implementation for kernel certification and the quantized serving arm,
    # so the two can never drift on what "within tolerance" means. Forward:
    # strict 5e-4. Gradient: 5e-3 — the ANALYTIC worst case of an
    # accurate-mean kernel, not slack. The sigma cotangent at a count-n
    # segment contributes d_std/(std*n)*(x-mu); at near-degenerate pairs
    # (std -> sqrt(eps) = 3.16e-3, the floor the forward pins) the factor
    # |quad| reaches 0.9/(2*sqrt(eps)) ~ 142, which amplifies the bf16x2
    # mean's ~1e-5 rounding to ~4e-3 in isolated elements regardless of
    # kernel quality (measured on v5e: 1.3e-3, located exactly at count-2
    # std~3.5e-3 segments; the XLA incumbent carries 0.11 at the same
    # elements). Anything above 5e-3 therefore indicates a real defect,
    # while a uniform 5e-4 would reject every f32-mean-based formula.
    from ..precision.tolerance import KERNEL_CERT_GATE

    verdict = KERNEL_CERT_GATE.check(
        max(max_err_fwd, wide_err_fwd), max(max_err_grad, wide_err_grad)
    )
    return {
        "backend": _platform(),
        "pallas_enabled": pallas_enabled(),
        "pallas_skip": pallas_skip_enabled(),
        "contiguous_ids": contiguous,
        "ok": verdict["ok"],
        "tol": KERNEL_CERT_GATE.fwd,
        "tol_grad": KERNEL_CERT_GATE.grad,
        "max_err_fwd": max_err_fwd,
        "max_err_grad": max_err_grad,
        "err_components": err_components,
        "xla_err_components": xla_components,
        "wide_f": f_wide,
        "wide_err_fwd": wide_err_fwd,
        "wide_err_grad": wide_err_grad,
        "xla_err_fwd": xla_err_fwd,
        "xla_err_grad": xla_err_grad,
        "pallas_ms": round(pallas_ms, 4),
        "xla_ms": round(xla_ms, 4),
        "speedup": round(xla_ms / pallas_ms, 3),
        **(sorted_res or {}),
    }


def _flatten_trailing(data):
    """[E, ...] → ([E, F], unflatten) for the 2-D kernel."""
    if data.ndim == 2:
        return data, lambda x: x
    shape = data.shape
    if data.ndim == 1:
        return data[:, None], lambda x: x[:, 0]
    return data.reshape(shape[0], -1), lambda x: x.reshape(
        (x.shape[0],) + shape[1:]
    )


def localize_row_ptr(row_ptr, axis_name, num_local_edges: int):
    """Global CSR boundaries → THIS edge shard's local boundaries (graftmesh
    halo/edge-cut contract, docs/DISTRIBUTED.md).

    Edge-sharded graph parallelism slices the destination-sorted edge list
    into equal contiguous shards (shard_map's even split over the edge axis),
    so shard ``s`` owns global rows ``[s*E_loc, (s+1)*E_loc)`` and a node's
    local run is the global run clamped into that window::

        local_row_ptr[n] = clip(global_row_ptr[n] - s*E_loc, 0, E_loc)

    Nodes whose edges live entirely on another shard get an empty local run
    (left == right), nodes cut by the shard boundary get exactly their local
    rows — the subsequent psum over ``axis_name`` is the halo exchange that
    sums each node's per-shard partial aggregates. Must be called INSIDE the
    sharded computation (``lax.axis_index`` needs the bound axis)."""
    start = jax.lax.axis_index(axis_name).astype(jnp.int32) * jnp.int32(
        num_local_edges
    )
    return jnp.clip(
        row_ptr.astype(jnp.int32) - start, 0, jnp.int32(num_local_edges)
    )


def _sorted_route(sorted_ids: bool, row_ptr, axis_name, num_local_edges=None):
    """ONE resolution of the sorted/CSR dispatch every fused wrapper uses.

    Returns ``(use_sorted, use_csr_kernel, row_ptr)``: the sorted prefix
    path when enabled (precedence unchanged from r05), else the CSR
    run-walk kernel when the caller supplied boundaries under the
    HYDRAGNN_PALLAS opt-in. Under an ``axis_name`` the global ``row_ptr``
    offsets are wrong for a local edge shard: since graftmesh they are
    LOCALIZED per shard (:func:`localize_row_ptr` — the caller passes its
    local edge count) so graph-partitioned steps stay zero-searchsorted;
    a caller that cannot name its local edge count falls back to the local
    re-derivation (row_ptr nulled). Centralized so a routing change cannot
    silently diverge between wrappers (a missed site would send that
    wrapper's traffic back to the scatter path — the 0.47x regression class
    the contract checker guards against)."""
    if axis_name is not None and row_ptr is not None:
        if num_local_edges is None:
            row_ptr = None
        else:
            row_ptr = localize_row_ptr(row_ptr, axis_name, num_local_edges)
    use_sorted = sorted_ids and srt.sorted_enabled()
    use_csr_kernel = (
        not use_sorted
        and sorted_ids
        and row_ptr is not None
        and csr_kernel_enabled()
    )
    return use_sorted, use_csr_kernel, row_ptr


def _arm(use_sorted, use_csr_kernel, row_ptr, otherwise=None) -> str:
    """The route as the scope names it (telemetry/scopes.py AGG_ARMS), from
    what :func:`_sorted_route` resolved."""
    if use_sorted:
        return "csr" if row_ptr is not None else "sorted"
    if use_csr_kernel:
        return "pallas_csr"
    return otherwise or ("pallas" if pallas_enabled() else "xla")


def fused_segment_sum(
    data, segment_ids, num_segments: int, mask=None, axis_name=None,
    sorted_ids: bool = False, row_ptr=None,
):
    """Drop-in masked ``segment_sum`` used by every conv family's aggregation:
    the scatter-free sorted path when the caller guarantees non-decreasing
    ids AND HYDRAGNN_SEGMENT_SORTED=1 (with ``row_ptr`` — the CSR batch
    contract — consuming precomputed boundaries instead of searching), the
    CSR run-walk or one-hot MXU kernel when opted in (HYDRAGNN_PALLAS=1 —
    see pallas_enabled for why the default is the XLA path since r05), the
    masked XLA segment op otherwise. Accepts any [E, ...] float data
    (trailing dims flattened for the kernel)."""
    total, _ = _fused_sum_count(
        "sum", data, segment_ids, num_segments, mask, axis_name, sorted_ids,
        row_ptr,
    )
    return total


def fused_segment_sum_count(
    data, segment_ids, num_segments: int, mask=None, axis_name=None,
    sorted_ids: bool = False, row_ptr=None,
):
    """Masked (segment_sum, segment_count) in ONE fused pass — callers that
    need both (MFC's degree lookup) save a whole scatter. Falls back to the
    two XLA ops off-TPU.

    ``sorted_ids=True`` declares the collation contract: non-decreasing ids
    with masked rows targeting padding segments (whose outputs are unused) —
    the sorted path's count includes masked rows, which is only correct
    under that contract. ``row_ptr`` carries the contract's precomputed CSR
    boundaries (LOCALIZED per shard under ``axis_name`` — graftmesh's
    halo/edge-cut contract, see :func:`localize_row_ptr`)."""
    return _fused_sum_count(
        "sum_count", data, segment_ids, num_segments, mask, axis_name,
        sorted_ids, row_ptr,
    )


def _fused_sum_count(
    what, data, segment_ids, num_segments, mask, axis_name, sorted_ids, row_ptr
):
    """:func:`fused_segment_sum_count` under the scope of the entry point that
    was called (``what``: sum, sum_count) and the arm the route resolved."""
    use_sorted, use_csr_kernel, row_ptr = _sorted_route(
        sorted_ids, row_ptr, axis_name, num_local_edges=segment_ids.shape[0]
    )
    with scopes.agg_scope(what, _arm(use_sorted, use_csr_kernel, row_ptr)):
        if use_sorted or use_csr_kernel:
            # Sorted/CSR contract prep: zero masked rows, RAW (sorted) ids.
            srt.attach_layout_check(segment_ids)
            flat, unflatten = _flatten_trailing(data)
            if mask is not None:
                flat = jnp.where(mask[:, None], flat, 0)
            if use_sorted:
                total, count = srt.segment_sum_count_auto(
                    flat.astype(jnp.float32), segment_ids.astype(jnp.int32),
                    num_segments, row_ptr=row_ptr,
                )
                if axis_name is not None:
                    total = jax.lax.psum(total, axis_name)
                    count = jax.lax.psum(count, axis_name)
            else:
                # CSR run-walk kernel (HYDRAGNN_PALLAS opt-in, row_ptr present).
                total, count = csr_segment_sum_count(
                    flat.astype(jnp.float32), row_ptr,
                    segment_ids.astype(jnp.int32), num_segments,
                    _platform() != "tpu", _wants_split(flat.dtype),
                )
            return unflatten(total.astype(data.dtype)), count
        if not pallas_enabled():
            return (
                seg.segment_sum(
                    data, segment_ids, num_segments, mask=mask, axis_name=axis_name
                ),
                seg.segment_count(
                    segment_ids, num_segments, mask=mask, axis_name=axis_name
                ),
            )
        flat, unflatten = _flatten_trailing(data)
        ids = segment_ids.astype(jnp.int32)
        if mask is not None:
            ids = jnp.where(mask, ids, -1)
        total, count = segment_sum_count(
            flat, ids, num_segments, _platform() != "tpu", _wants_split(flat.dtype)
        )
        if axis_name is not None:
            total = jax.lax.psum(total, axis_name)
            count = jax.lax.psum(count, axis_name)
        return unflatten(total.astype(data.dtype)), count


def fused_segment_mean(
    data, segment_ids, num_segments: int, mask=None, axis_name=None,
    sorted_ids: bool = False, row_ptr=None,
):
    """Drop-in masked ``segment_mean`` over the fused kernel (SAGE neighbor
    mean, the global mean-pool readout). Both paths return ``data.dtype`` so
    CPU-fallback and TPU runs agree on dtype flow."""
    # Route decision only — the UN-localized row_ptr forwards to
    # fused_segment_sum_count, which performs the per-shard localization
    # itself (localizing here too would shift the boundaries twice).
    use_sorted, use_csr_kernel, local_ptr = _sorted_route(
        sorted_ids, row_ptr, axis_name, num_local_edges=segment_ids.shape[0]
    )
    with scopes.agg_scope("mean", _arm(use_sorted, use_csr_kernel, local_ptr)):
        if use_sorted or use_csr_kernel:
            total, count = fused_segment_sum_count(
                data, segment_ids, num_segments, mask=mask, axis_name=axis_name,
                sorted_ids=True, row_ptr=row_ptr,
            )
            safe = jnp.maximum(count, 1.0).reshape(
                count.shape + (1,) * (total.ndim - count.ndim)
            )
            return (total / safe).astype(data.dtype)
        if not pallas_enabled():
            return seg.segment_mean(
                data, segment_ids, num_segments, mask=mask, axis_name=axis_name
            ).astype(data.dtype)
        total, count = fused_segment_sum_count(
            data, segment_ids, num_segments, mask=mask, axis_name=axis_name
        )
        safe = jnp.maximum(count, 1.0).reshape(
            count.shape + (1,) * (total.ndim - count.ndim)
        )
        return (total / safe).astype(data.dtype)


def fused_segment_softmax(
    logits, segment_ids, num_segments: int, mask=None, axis_name=None,
    sorted_ids: bool = False, row_ptr=None,
):
    """Generic segment softmax with the denominator's scatter on the fused
    MXU kernel or the scatter-free sorted/CSR path — one shared
    stabilization body (seg.segment_softmax) with the sum injected, so the
    TPU and fallback paths cannot drift. The per-segment max stays on XLA
    ``segment_max`` (extrema can't ride the MXU) under stop_gradient, so no
    scatter appears in the backward either.

    NOTE: GATv2Conv no longer routes through here — its softmax runs over
    {incoming edges} ∪ {self} and is built inline from seg.segment_max +
    fused_segment_sum so the dense self term can join the denominator
    (models/convs.py:GATv2Conv). This stays the entry point for plain
    edge-only segment softmaxes; ``sorted_ids``/``row_ptr`` declare the CSR
    batch contract for the denominator sum."""
    use_sorted, use_csr_kernel, local_ptr = _sorted_route(
        sorted_ids, row_ptr, axis_name, num_local_edges=segment_ids.shape[0]
    )
    use_fast = pallas_enabled() or use_sorted or use_csr_kernel
    sum_fn = None
    if use_fast:
        def sum_fn(d, i, n, mask=None, axis_name=None):
            return fused_segment_sum(
                d, i, n, mask=mask, axis_name=axis_name,
                sorted_ids=sorted_ids, row_ptr=row_ptr,
            )
    # The arm is the denominator sum's; the max is XLA's on every arm.
    with scopes.agg_scope("softmax", _arm(use_sorted, use_csr_kernel, local_ptr)):
        return seg.segment_softmax(
            logits, segment_ids, num_segments, mask=mask, axis_name=axis_name,
            sum_fn=sum_fn,
        )


def pna_aggregate(
    msg: jnp.ndarray,
    receivers: jnp.ndarray,
    num_segments: int,
    aggregators: Tuple[str, ...],
    mask: Optional[jnp.ndarray] = None,
    axis_name: Optional[str] = None,
    sorted_ids: bool = False,
    row_ptr=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PNA multi-aggregator bundle → (stacked [N, A, F] aggregates, count [N]).

    Routes the sum/mean/std family through the scatter-free sorted path
    (precomputed CSR boundaries when ``row_ptr`` is present) or the fused
    Pallas kernel when enabled. min/max: on the sorted path with ``row_ptr``
    and no ``axis_name``, the one-pass scan kernel over receiver runs
    (:func:`_extrema_csr`); XLA's segment extrema on every other route (no
    boundaries; an edge-sharded axis, where a run is cut across shards; off
    the sorted path). Falls back entirely to the masked XLA segment ops
    otherwise.
    """
    with jax.named_scope(scopes.AGG_PNA):
        n = num_segments
        use_sorted = sorted_ids and srt.sorted_enabled()
        # The scan kernel wants whole runs: an edge-sharded axis cuts them.
        extrema_ptr = row_ptr if use_sorted and axis_name is None else None
        if pallas_enabled() or use_sorted:
            fused = {}
            count = None
            if any(a in ("mean", "std", "sum") for a in aggregators):
                total, mean, std, count = fused_segment_stats(
                    msg, receivers, n, mask=mask, axis_name=axis_name,
                    want_std="std" in aggregators, sorted_ids=sorted_ids,
                    row_ptr=row_ptr,
                )
                fused = {"mean": mean, "std": std, "sum": total}
            if "min" in aggregators or "max" in aggregators:
                ids = receivers.astype(jnp.int32)
                # CSR contract: RAW sorted ids, the masked edges' rows in the
                # padding node's run. The scatters take -1 for a masked row.
                if extrema_ptr is None and mask is not None:
                    ids = jnp.where(mask, ids, -1)
                mn, mx = segment_extrema(msg, ids, n, axis_name, extrema_ptr)
                fused["min"], fused["max"] = mn, mx
        else:
            fused = {}
            count = None
        aggs = []
        for a in aggregators:
            if a in fused:
                aggs.append(fused[a])
            elif a == "mean":
                aggs.append(seg.segment_mean(msg, receivers, n, mask=mask, axis_name=axis_name))
            elif a == "sum":
                aggs.append(seg.segment_sum(msg, receivers, n, mask=mask, axis_name=axis_name))
            elif a == "std":
                aggs.append(seg.segment_std(msg, receivers, n, mask=mask, axis_name=axis_name))
            elif a == "min":
                aggs.append(seg.segment_min(msg, receivers, n, mask=mask, axis_name=axis_name))
            elif a == "max":
                aggs.append(seg.segment_max(msg, receivers, n, mask=mask, axis_name=axis_name))
            else:
                raise ValueError(f"Unknown aggregator {a}")
        if count is None:
            count = seg.segment_count(receivers, n, mask=mask, axis_name=axis_name)
        return jnp.stack(aggs, axis=1), count
