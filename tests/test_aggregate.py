"""The aggregation entry points of ``hydragnn_tpu/ops/aggregate.py`` on the
arms that exist, against the masked XLA segment ops of ``ops/segment.py`` and,
where those are themselves inexact, against float64.

``route`` puts the chip's arm under this CPU through the one override
(``HYDRAGNN_SEGMENT_SORTED=1``): ``sorted`` is the arm without the batch's
``row_ptr``, ``csr`` the arm with it. Problems follow the batch contract: ids
non-decreasing, the masked rows in the last (padding) segment's run, whose
outputs nobody reads. Values and routes, never a time."""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.graphs.csr import build_row_ptr
from hydragnn_tpu.ops import aggregate as agg
from hydragnn_tpu.ops import certify
from hydragnn_tpu.ops import segment as seg
from tests.conftest import program

ROUTES = ("sorted", "csr")
# The prefix sums' error at these sizes (ops/segment_sorted.py: compensated,
# ~1e-5 absolute), well inside the 5e-4 gate of ops/certify.py.
_ATOL = 3e-4
_RTOL = 1e-4


@pytest.fixture
def route(request, monkeypatch):
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    return request.param


def _problem(rng, route, e=300, n=40, f=17, padding=40):
    """(data, ids, mask, n, row_ptr, real): ``padding`` masked rows at the end,
    in the run of segment ``n - 1``; ``real`` the segments somebody reads."""
    ids = np.sort(rng.integers(0, n - 1, size=e)).astype(np.int32)
    ids[e - padding:] = n - 1
    mask = np.arange(e) < e - padding
    data = rng.normal(size=(e, f)).astype(np.float32)
    row_ptr = jnp.asarray(build_row_ptr(ids, n)) if route == "csr" else None
    return (
        jnp.asarray(data), jnp.asarray(ids), jnp.asarray(mask), n, row_ptr,
        np.arange(n) < n - 1,
    )


def _run(fn, *args):
    """``fn(*args)`` as ONE compiled program, traced at THIS call under the
    route the test has set (``tests/conftest.py`` ``program``), its outputs
    on the host: both sides of a comparison go the same way."""
    return jax.tree_util.tree_map(np.asarray, program(fn)(*args))


def _arms(fn, *args):
    """The arms (``telemetry/scopes.py`` ``AGG_ARMS``) in the scopes of
    ``fn``'s lowering as the switches stand now."""
    text = program(fn).lower(*args).as_text(debug_info=True)
    return set(re.findall(r"hydragnn\.agg\.\w+\.(\w+)", text))


def _xla_stats(ids, n, mask=None):
    """``d -> (sum, mean, std, count)`` by the masked XLA segment ops."""
    return lambda d: (
        seg.segment_sum(d, ids, n, mask=mask), seg.segment_mean(d, ids, n, mask=mask),
        seg.segment_std(d, ids, n, mask=mask), seg.segment_count(ids, n, mask=mask),
    )


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def pytest_fused_stats_match_xla(route):
    data, ids, mask, n, row_ptr, real = _problem(
        np.random.default_rng(1), route, e=257, n=33, f=5
    )
    total, mean, std, count = _run(
        lambda d: agg.fused_segment_stats(d, ids, n, mask=mask, row_ptr=row_ptr), data
    )
    *refs, ref_count = _run(_xla_stats(ids, n, mask), data)
    for got, ref in zip((total, mean, std), refs):
        np.testing.assert_allclose(got[real], ref[real], rtol=_RTOL, atol=_ATOL)
    np.testing.assert_array_equal(count[real], ref_count[real])
    # The contract's other half: the masked rows ARE counted, in the padding
    # segment, and nowhere else.
    assert float(count[n - 1]) == 40.0


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def pytest_fused_stats_gradient_matches_xla(route):
    data, ids, mask, n, row_ptr, real = _problem(
        np.random.default_rng(2), route, e=64, n=10, f=4, padding=9
    )
    w = jnp.asarray(real, jnp.float32)[:, None]

    def fused_loss(d):
        _, mean, std, _ = agg.fused_segment_stats(
            d, ids, n, mask=mask, row_ptr=row_ptr
        )
        return jnp.sum(w * mean * 1.3) + jnp.sum(w * std * 0.7)

    def xla_loss(d):
        mean = seg.segment_mean(d, ids, n, mask=mask)
        std = seg.segment_std(d, ids, n, mask=mask)
        return jnp.sum(w * mean * 1.3) + jnp.sum(w * std * 0.7)

    g_fused = _run(jax.grad(fused_loss), data)
    np.testing.assert_allclose(
        g_fused, _run(jax.grad(xla_loss), data), rtol=1e-4, atol=1e-5
    )
    assert not g_fused[~np.asarray(mask)].any()


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def pytest_pna_aggregate_matches_the_xla_composition(route, monkeypatch):
    """``pna_aggregate`` on the sorted arm against itself off it (the masked
    XLA ops), on every segment somebody reads."""
    data, ids, mask, n, row_ptr, real = _problem(
        np.random.default_rng(3), route, e=120, n=16, f=8, padding=14
    )
    aggregators = ("mean", "min", "max", "std")

    def bundle(d):
        return agg.pna_aggregate(d, ids, n, aggregators, mask=mask, row_ptr=row_ptr)

    got, cnt = _run(bundle, data)
    on = _arms(bundle, data)
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "0")
    want, cnt_xla = _run(bundle, data)
    # Each side was traced on its own arm (the prefix sums, with the scan
    # kernel for the extrema where the boundaries are given, against XLA's
    # segment ops), and not one program against itself.
    assert on == {"sorted": {"sorted", "xla"}, "csr": {"csr", "pallas_csr"}}[route]
    assert _arms(bundle, data) == {"xla"}
    np.testing.assert_allclose(got[real], want[real], rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(cnt[real], cnt_xla[real])


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def pytest_centered_std_beats_uncentered_on_degenerate_segments(route):
    """``std`` from centered values against XLA's
    ``sqrt(relu(E[x^2]-E[x]^2)+eps)``, which cancels catastrophically in f32
    when a segment's values cluster round a large offset; both against a
    float64 reference of the centered form."""
    rng = np.random.default_rng(7)
    e, n, f = 512, 64, 4
    base = rng.normal(size=(n,)) * 50
    ids_np = np.sort(rng.integers(0, n, size=e))
    data64 = base[ids_np][:, None] + rng.normal(size=(e, f)) * 1e-3
    ids = jnp.asarray(ids_np.astype(np.int32))
    data = jnp.asarray(data64.astype(np.float32))
    row_ptr = jnp.asarray(build_row_ptr(ids_np, n)) if route == "csr" else None

    ref = np.full((n, f), np.sqrt(1e-5))
    for s in range(n):
        rows = data64[ids_np == s]
        if len(rows):
            ref[s] = np.sqrt(rows.var(axis=0) + 1e-5)

    std_fused = _run(lambda d: agg.fused_segment_stats(d, ids, n, row_ptr=row_ptr)[2], data)
    std_xla = _run(lambda d: seg.segment_std(d, ids, n), data)
    err_fused = float(np.abs(std_fused.astype(np.float64) - ref).max())
    err_xla = float(np.abs(std_xla.astype(np.float64) - ref).max())
    assert err_fused < 1e-4, err_fused
    assert err_fused < err_xla  # strictly better than the uncentered form


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def pytest_fused_dropin_wrappers_match_xla(route):
    """``fused_segment_sum`` / ``_mean`` / ``_sum_count`` (what every conv
    family calls) against the masked XLA ops, with 3-D data and a bf16 input
    whose dtype the output must keep."""
    rng = np.random.default_rng(1)
    data, ids, mask, n, row_ptr, real = _problem(rng, route)

    def fused_sum(d):
        return agg.fused_segment_sum(d, ids, n, mask=mask, row_ptr=row_ptr)

    got_sum, got_mean, (_, count) = _run(
        lambda d: (
            fused_sum(d),
            agg.fused_segment_mean(d, ids, n, mask=mask, row_ptr=row_ptr),
            agg.fused_segment_sum_count(d, ids, n, mask=mask, row_ptr=row_ptr),
        ),
        data,
    )
    ref_sum, ref_mean, _, ref_count = _run(_xla_stats(ids, n, mask), data)
    np.testing.assert_allclose(got_sum[real], ref_sum[real], rtol=_RTOL, atol=_ATOL)
    np.testing.assert_allclose(got_mean[real], ref_mean[real], rtol=_RTOL, atol=_ATOL)
    np.testing.assert_array_equal(count[real], ref_count[real])

    # 3-D ([E, h, f], the trailing dims flattened inside); no mask.
    d3 = jnp.asarray(rng.normal(size=(300, 3, 5)).astype(np.float32))
    np.testing.assert_allclose(
        _run(lambda d: agg.fused_segment_sum(d, ids, n, row_ptr=row_ptr), d3),
        _run(lambda d: seg.segment_sum(d, ids, n), d3), rtol=_RTOL, atol=_ATOL,
    )

    # bf16 in → bf16 out (mixed-precision dtype flow preserved).
    assert jax.eval_shape(fused_sum, data.astype(jnp.bfloat16)).dtype == jnp.bfloat16

    # Gradients flow (gather backward), masked rows get zero cotangent.
    g = _run(jax.grad(lambda d: fused_sum(d).sum()), data)
    g_ref = _run(jax.grad(lambda d: seg.segment_sum(d, ids, n, mask=mask).sum()), data)
    np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def pytest_fused_segment_softmax_matches_xla(route):
    """``fused_segment_softmax`` == ``seg.segment_softmax``, values and
    gradients, with masking; the sorted arm's sum is the denominator's."""
    rng = np.random.default_rng(2)
    _, ids, mask, n, row_ptr, _ = _problem(rng, route, e=200, n=30, f=1, padding=25)
    logits = jnp.asarray(rng.normal(size=(200, 6)).astype(np.float32) * 3)

    def fused(l):
        return agg.fused_segment_softmax(l, ids, n, mask=mask, row_ptr=row_ptr)

    a = _run(fused, logits)
    b = _run(lambda l: seg.segment_softmax(l, ids, n, mask=mask), logits)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    kept = np.asarray(mask)
    assert a[kept].sum() > 0 and not a[~kept].any()

    ga = _run(jax.grad(lambda l: (fused(l) ** 2).sum()), logits)
    gb = _run(jax.grad(lambda l: (seg.segment_softmax(l, ids, n, mask=mask) ** 2).sum()), logits)
    np.testing.assert_allclose(ga, gb, rtol=1e-4, atol=1e-6)
    text = jax.jit(fused).lower(logits).as_text(debug_info=True)
    assert f"hydragnn.agg.softmax.{route}" in text


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def pytest_fused_ops_differentiable_under_shard_map(route):
    """Graph-parallel backward through the sorted arm, the boundaries searched
    in each shard's own rows (``sorted``) or the batch's ``row_ptr`` localized
    a shard (``csr``): the gradient flows through ``shard_map`` over a 'graph'
    axis and equals the one-device gradient. Jitted, as every step of the
    program is (outside ``jit`` the zero-size dtype carrier in
    ``segment_sorted``'s residuals is refused a sharding: ROADMAP D20)."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("graph",))
    e, n, h = 64, 10, 3
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))
    ids_np = np.sort(rng.integers(0, n, size=e)).astype(np.int32)
    ids, row_ptr = jnp.asarray(ids_np), jnp.asarray(build_row_ptr(ids_np, n))
    use = (lambda ptr: ptr) if route == "csr" else (lambda ptr: None)

    def terms(l_, ids_, ptr, axis_name):
        """(what is whole on every shard after its psum, what is this shard's)."""
        s, c = agg.fused_segment_sum_count(
            l_, ids_, n, axis_name=axis_name, row_ptr=ptr
        )
        a = agg.fused_segment_softmax(l_, ids_, n, axis_name=axis_name, row_ptr=ptr)
        m = agg.fused_segment_mean(l_, ids_, n, axis_name=axis_name, row_ptr=ptr)
        return (s ** 2).sum() + (m ** 2).sum(), (a ** 2).sum()

    def local(l_, ids_, ptr):
        whole, mine = terms(l_, ids_, use(ptr), "graph")
        return whole + jax.lax.psum(mine, "graph")

    f = jax.shard_map(
        local, mesh=mesh, in_specs=(P("graph"), P("graph"), P()), out_specs=P(),
        check_vma=False,
    )
    g = jax.jit(jax.grad(lambda l: f(l, ids, row_ptr)))(logits)
    g_one = _run(jax.grad(lambda l: sum(terms(l, ids, use(row_ptr), None))), logits)
    np.testing.assert_allclose(g, g_one, rtol=1e-4, atol=1e-5)


def _gather_problem(rng, shape, n=40, e=300, padding=60):
    """(table [n, *shape], ids, weights [e, *shape]): ids non-decreasing over
    every THIRD row of the table (so two in three runs are empty), the last
    ``padding`` rows in the padding row's run (``n - 1``): a long one."""
    ids = np.sort(rng.integers(0, (n - 1) // 3, size=e) * 3).astype(np.int32)
    ids[e - padding:] = n - 1
    table = rng.normal(size=(n,) + shape).astype(np.float32)
    weights = rng.normal(size=(e,) + shape).astype(np.float32)
    return jnp.asarray(table), ids, jnp.asarray(weights)


def _segment_sum_f64(rows, ids, n):
    truth = np.zeros((n,) + rows.shape[1:], np.float64)
    np.add.at(truth, ids, np.asarray(rows, np.float64))
    return truth


@pytest.mark.parametrize(
    "shape", [(1,), (6,), (128,), (6, 64)], ids=["w1", "w6", "w128", "w384"]
)
@pytest.mark.parametrize("arm", ("xla",) + ROUTES)
def pytest_gather_sorted_is_the_gather_and_its_gradient_the_sorted_sum(
    arm, shape, monkeypatch
):
    """``gather_sorted`` (a conv's receiver-side gather) against plain
    indexing, at GATv2's and PNA's widths (PNA's input column, GATv2's six
    denominators, one lane tile, GATv2's ``[6, 64]`` rows), over ids with
    empty runs and a long padding run. The value is ``table[ids]`` to the bit
    on every arm. Off the sorted arm the gradient is plain indexing's to the
    bit; on it the gradient is the forward sums' route by the row's width,
    held to float64: the prefix sums' bound under ``WIDE_ROW`` columns, a
    sequential float32 sum's from there up (the error of adding a run's rows
    in row order onto zero, relative to the largest entry)."""
    if arm == "xla":
        monkeypatch.delenv("HYDRAGNN_SEGMENT_SORTED", raising=False)
    else:
        monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    table, ids_np, weights = _gather_problem(np.random.default_rng(46), shape)
    n, ids = table.shape[0], jnp.asarray(ids_np)
    row_ptr = jnp.asarray(build_row_ptr(ids_np, n)) if arm == "csr" else None

    def got(t):
        return agg.gather_sorted(t, ids, row_ptr)

    assert np.array_equal(np.asarray(jax.jit(got)(table)), np.asarray(table)[ids_np])
    grad = jax.jit(jax.grad(lambda t: jnp.sum(got(t) * weights)))(table)
    plain = jax.jit(jax.grad(lambda t: jnp.sum(t[ids] * weights)))(table)
    assert grad.dtype == table.dtype and grad.shape == table.shape
    if arm == "xla":
        assert np.array_equal(np.asarray(grad), np.asarray(plain))
        return
    truth = _segment_sum_f64(weights, ids_np, n)
    if math.prod(shape) >= 128:
        err = np.abs(np.asarray(grad, np.float64) - truth).max()
        assert err <= 1e-6 * np.abs(truth).max(), err
    else:
        np.testing.assert_allclose(grad, truth, rtol=_RTOL, atol=_ATOL)
    # bf16 table in, bf16 gradient out (the sum itself runs in float32).
    half = jax.jit(jax.grad(lambda t: jnp.sum(got(t).astype(jnp.float32) * weights)))(
        table.astype(jnp.bfloat16)
    )
    assert half.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(half, np.float64), truth, rtol=2e-2, atol=2e-2 * np.abs(truth).max()
    )


@pytest.mark.parametrize("route", ROUTES, indirect=True)
def pytest_gather_sorted_reduces_the_table_gradient_once_under_shard_map(route):
    """Under an edge-sharded axis the table is whole on every shard and its
    rows are gathered by each shard's own ids. The backward is the LOCAL sum
    (this shard's rows, this shard's boundaries) and holds no collective: the
    transpose of the replicated table's use reduces it across the shards, so
    the gradient equals plain indexing's in the same harness and the
    one-device truth, narrow route and wide, and no ``all-reduce`` of the
    compiled program sits under ``hydragnn.gather``."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("graph",))
    use = (lambda ptr: ptr) if route == "csr" else (lambda ptr: None)
    for shape in ((6,), (128,)):
        table, ids_np, weights = _gather_problem(
            np.random.default_rng(7), shape, e=256, padding=40
        )
        n, ids = table.shape[0], jnp.asarray(ids_np)
        row_ptr = jnp.asarray(build_row_ptr(ids_np, n))

        def sharded(gather):
            def local(t, ids_, w_, ptr):
                return jax.lax.psum(jnp.sum(gather(t, ids_, ptr) * w_), "graph")

            f = jax.shard_map(
                local, mesh=mesh, in_specs=(P(), P("graph"), P("graph"), P()),
                out_specs=P(), check_vma=False,
            )
            return jax.jit(jax.grad(lambda t: f(t, ids, weights, row_ptr)))

        compiled = sharded(
            lambda t, i, ptr: agg.gather_sorted(t, i, use(ptr), "graph")
        ).lower(table).compile()  # compiled once: run here, read below
        grad = compiled(table)
        plain = sharded(lambda t, i, ptr: t[i])(table)
        np.testing.assert_allclose(grad, plain, rtol=_RTOL, atol=_ATOL)
        np.testing.assert_allclose(
            grad, _segment_sum_f64(weights, ids_np, n), rtol=_RTOL, atol=_ATOL
        )
        text = compiled.as_text()
        reduces = [
            line.split("metadata=")[1] for line in text.splitlines()
            if re.search(r"\sall-reduce\(", line.split("metadata=")[0])
        ]
        assert "hydragnn.gather" in text and reduces
        assert not [r for r in reduces if "hydragnn.gather" in r], reduces


@pytest.mark.parametrize("case", certify.WIDE_CASES)
def pytest_wide_route_holds_the_f64_truth(case, monkeypatch):
    """The sorted arm's WIDE sums (``segment_sorted.WIDE_ROW`` columns or
    more: one XLA scatter-add told the ids are sorted, arm ``scatter_sorted``)
    against numpy in float64 at ``[16384, 512]`` rows over 4096 segments,
    through PNA's stats bundle, forward and gradient, in every layout a batch
    can bring (``certify.WIDE_CASES``: short runs, boundaries searched, empty
    runs, a long zeroed padding run, 16347 rows, bfloat16 messages, an
    edge-sharded axis over two devices).

    The order it adds in: a run's rows one after another, in row order, onto
    a zero row; nothing is subtracted from a prefix. So the error of a segment
    is a sequential float32 sum's over ITS rows: at most (rows - 1) half-ulps
    of the partial sums. Messages ~N(1, 2) in runs of 4-12 rows: read 3.9e-6
    to 9.8e-6 here, held to 2e-5 (on the chip 1.6e-5 to 4.3e-5 in runs of 16
    and 48 at ``[262144, 512]``, and 4.5e-6 on the cells' unit-variance rows,
    where the prefix route reads 3.7e-4: PERF.md §6 PR 32); the certifier's
    pin is ``WIDE_FWD_PIN`` = 1e-4, a fifth of its gate. The gradient
    is gathers through the ids (3.4e-5, the ``std`` term's; bfloat16: its own
    rounding, 2^-8 of the largest entry)."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    report = certify.certify_wide_sum(case, e=16384, f=512, n=4096)
    assert report["shape"]["e"] == (16347 if case == "ragged_rows" else 16384)
    assert report["err_fwd"] < 2e-5 < certify.WIDE_FWD_PIN, report
    assert report["err_grad"] <= report["tol_grad"], report
    if case != "bf16":
        assert report["err_grad"] < 5e-4, report
    assert report["ok"], report


def pytest_wide_route_engages_by_width_alone(monkeypatch):
    """``WIDE_ROW`` is one lane tile, read off the shape: the arm of a sum is
    ``scatter_sorted`` from that width up with or without ``row_ptr``, the
    prefix arms below it, ``xla`` off the sorted arm at any width; the wide
    route refuses a narrow call and the certification a CPU's default arm."""
    from hydragnn_tpu.ops import segment_sorted as srt

    assert srt.WIDE_ROW == 128
    ptr = jnp.zeros((5,), jnp.int32)
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    for width, with_ptr, without in (
        (1, "csr", "sorted"), (6, "csr", "sorted"), (127, "csr", "sorted"),
        (128, "scatter_sorted", "scatter_sorted"),
        (512, "scatter_sorted", "scatter_sorted"),
    ):
        assert agg._arm(ptr, width) == with_ptr, width
        assert agg._arm(None, width) == without, width
    assert agg._width(jnp.zeros((8, 6, 64))) == 384
    assert agg._width(jnp.zeros((8,))) == 1
    with pytest.raises(ValueError, match="not a wide case"):
        certify.certify_wide_sum("short_runs", e=64, f=64, n=8)
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "0")
    assert agg._arm(ptr, 512) == "xla"
    with pytest.raises(RuntimeError, match="sorted arm"):
        certify.certify_wide_sum("short_runs", e=64, f=128, n=8)


def pytest_no_flag_selects_an_aggregation_kernel():
    """The arm is decided from what the code can observe. The only environment
    variables anything under ``hydragnn_tpu/ops/`` reads are the sorted arm's
    override (the tests' seam, ROADMAP D17) and the layout check's switch."""
    ops = os.path.dirname(agg.__file__)
    modules = sorted(name for name in os.listdir(ops) if name.endswith(".py"))
    assert modules == [
        "__init__.py", "aggregate.py", "block_attention.py", "certify.py",
        "extrema_scan.py", "segment.py", "segment_sorted.py", "selective_scan.py",
    ]
    found = set()
    for name in modules:
        with open(os.path.join(ops, name)) as f:
            text = f.read()
        found |= set(re.findall(r"\bHYDRAGNN_[A-Z0-9_]+", text))
        reads = re.findall(r"os\.environ|getenv", text)
        assert not reads or name == "segment_sorted.py", (name, reads)
    assert found == {"HYDRAGNN_SEGMENT_SORTED", "HYDRAGNN_DEBUG_LAYOUT"}, found


def pytest_pna_aggregate_off_the_sorted_arm_is_the_masked_xla_ops_bit_for_bit(
    monkeypatch,
):
    """Off the sorted arm (a CPU's default) nothing stands between PNA and
    ``ops/segment.py``: values and gradient equal to the bit, ``row_ptr`` or
    not, and no other arm's name in the bundle."""
    monkeypatch.delenv("HYDRAGNN_SEGMENT_SORTED", raising=False)
    rng = np.random.default_rng(5)
    data, ids, mask, n, row_ptr, _ = _problem(rng, "csr", e=120, n=16, f=8)
    aggregators = ("mean", "min", "max", "std", "sum")
    weights = jnp.asarray(rng.normal(size=(n, len(aggregators), 8)), jnp.float32)

    def composed(d):
        ops = {"mean": seg.segment_mean, "min": seg.segment_min,
               "max": seg.segment_max, "std": seg.segment_std,
               "sum": seg.segment_sum}
        return jnp.stack(
            [ops[a](d, ids, n, mask=mask) for a in aggregators], axis=1
        ), seg.segment_count(ids, n, mask=mask)

    def loss(fn):
        return lambda d: jnp.sum(fn(d)[0] * weights)

    for ptr in (row_ptr, None):
        def bundle(d):
            return agg.pna_aggregate(d, ids, n, aggregators, mask=mask, row_ptr=ptr)

        got, cnt = jax.jit(bundle)(data)
        want, want_cnt = jax.jit(composed)(data)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert np.array_equal(np.asarray(cnt), np.asarray(want_cnt))
        assert np.array_equal(
            np.asarray(jax.jit(jax.grad(loss(bundle)))(data)),
            np.asarray(jax.jit(jax.grad(loss(composed)))(data)),
        )
        assert _arms(bundle, data) == {"xla"}
