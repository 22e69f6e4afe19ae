"""Parity tests for the fused Pallas segment kernel
(hydragnn_tpu/ops/pallas_segment.py) against the reference XLA segment ops —
run through the Pallas interpreter on the CPU test platform, exactly the
program the compiled kernel executes on TPU."""

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.ops import pallas_segment as ps
from hydragnn_tpu.ops import segment as seg


def _random_problem(rng, e=300, n=40, f=17):
    data = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, n, size=e).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.3)
    return data, ids, mask, n


# Kernel-vs-XLA value tolerance: the split path rounds the lo residual to
# bf16 explicitly (hardware-faithful — the MXU truncates f32 operands to bf16
# at DEFAULT dot precision), so the interpreter now shows the genuine bf16x2
# error ~ sum_k |x_k|*2^-17 per segment instead of exact f32. 3e-4 bounds
# that for every problem in this file and stays below the 5e-4 certification
# gate certify_pallas enforces.
_ATOL = 3e-4
_RTOL = 1e-4


def pytest_sum_count_match_xla():
    rng = np.random.default_rng(0)
    data, ids, mask, n = _random_problem(rng)
    masked_ids = jnp.where(mask, ids, -1)
    s, c = ps.segment_sum_count(data, masked_ids, n, True)
    np.testing.assert_allclose(
        s, seg.segment_sum(data, ids, n, mask=mask), rtol=_RTOL, atol=_ATOL
    )
    np.testing.assert_allclose(c, seg.segment_count(ids, n, mask=mask), rtol=1e-6)


def pytest_sum_count_empty_segments():
    # Segments with no edges must come back exactly zero.
    data = jnp.ones((4, 3), jnp.float32)
    ids = jnp.asarray([0, 0, 2, 2], jnp.int32)
    s, c = ps.segment_sum_count(data, ids, 5, True)
    np.testing.assert_array_equal(c, [2.0, 0.0, 2.0, 0.0, 0.0])
    np.testing.assert_array_equal(s[1], np.zeros(3))
    np.testing.assert_array_equal(s[4], np.zeros(3))


def pytest_fused_stats_match_xla():
    rng = np.random.default_rng(1)
    data, ids, mask, n = _random_problem(rng, e=257, n=33, f=5)
    total, mean, std, count = ps.fused_segment_stats(
        data, ids, n, mask=mask, interpret=True
    )
    np.testing.assert_allclose(
        total, seg.segment_sum(data, ids, n, mask=mask), rtol=_RTOL, atol=_ATOL
    )
    np.testing.assert_allclose(
        mean, seg.segment_mean(data, ids, n, mask=mask), rtol=_RTOL, atol=_ATOL
    )
    np.testing.assert_allclose(
        std, seg.segment_std(data, ids, n, mask=mask), rtol=_RTOL, atol=_ATOL
    )
    np.testing.assert_allclose(count, seg.segment_count(ids, n, mask=mask), rtol=1e-6)


def pytest_fused_stats_gradient_matches_xla():
    rng = np.random.default_rng(2)
    data, ids, mask, n = _random_problem(rng, e=64, n=10, f=4)

    def fused_loss(d):
        _, mean, std, _ = ps.fused_segment_stats(d, ids, n, mask=mask, interpret=True)
        return jnp.sum(mean * 1.3) + jnp.sum(std * 0.7)

    def xla_loss(d):
        mean = seg.segment_mean(d, ids, n, mask=mask)
        std = seg.segment_std(d, ids, n, mask=mask)
        return jnp.sum(mean * 1.3) + jnp.sum(std * 0.7)

    g_fused = jax.grad(fused_loss)(data)
    g_xla = jax.grad(xla_loss)(data)
    np.testing.assert_allclose(g_fused, g_xla, rtol=1e-4, atol=1e-5)


def pytest_pna_aggregate_fallback_matches_fused():
    """pna_aggregate must produce identical results whether the fused kernel is
    enabled (interpreter on CPU) or the XLA fallback runs."""
    rng = np.random.default_rng(3)
    data, ids, mask, n = _random_problem(rng, e=120, n=16, f=8)
    aggregators = ("mean", "min", "max", "std")

    import os

    saved = os.environ.get("HYDRAGNN_PALLAS")
    try:
        os.environ["HYDRAGNN_PALLAS"] = "1"
        agg_fused, cnt_fused = ps.pna_aggregate(data, ids, n, aggregators, mask=mask)
        os.environ["HYDRAGNN_PALLAS"] = "0"
        agg_xla, cnt_xla = ps.pna_aggregate(data, ids, n, aggregators, mask=mask)
    finally:
        if saved is None:
            os.environ.pop("HYDRAGNN_PALLAS", None)
        else:
            os.environ["HYDRAGNN_PALLAS"] = saved
    np.testing.assert_allclose(agg_fused, agg_xla, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(cnt_fused, cnt_xla, rtol=1e-6)


def pytest_centered_std_beats_uncentered_on_degenerate_segments():
    """The fused path computes std from centered values; XLA's
    sqrt(relu(E[x^2]-E[x]^2)+eps) cancels catastrophically in f32 when segment
    values cluster around a large offset. Both are compared against an f64
    reference built from the same centered math in numpy."""
    rng = np.random.default_rng(7)
    e, n, f = 512, 64, 4
    base = rng.normal(size=(n,)) * 50
    ids_np = rng.integers(0, n, size=e)
    data64 = base[ids_np][:, None] + rng.normal(size=(e, f)) * 1e-3
    ids = jnp.asarray(ids_np.astype(np.int32))
    data = jnp.asarray(data64.astype(np.float32))

    # f64 reference
    ref = np.zeros((n, f))
    for s in range(n):
        rows = data64[ids_np == s]
        if len(rows):
            ref[s] = np.sqrt(rows.var(axis=0) + 1e-5)
        else:
            ref[s] = np.sqrt(1e-5)

    _, _, std_fused, _ = ps.fused_segment_stats(data, ids, n, interpret=True)
    std_xla = seg.segment_std(data, ids, n)
    err_fused = float(np.abs(np.asarray(std_fused, np.float64) - ref).max())
    err_xla = float(np.abs(np.asarray(std_xla, np.float64) - ref).max())
    assert err_fused < 1e-4, err_fused
    assert err_fused < err_xla  # strictly better than the uncentered form


def pytest_fused_dropin_wrappers_match_xla(monkeypatch):
    """fused_segment_sum/mean (the drop-ins every conv family now routes
    through) must match the masked XLA ops — incl. 3-D GAT-shaped data and a
    bf16 input whose output dtype must be preserved."""
    monkeypatch.setenv("HYDRAGNN_PALLAS", "1")  # force the kernel (interpreter off-TPU)
    rng = np.random.default_rng(1)
    data, ids, mask, n = _random_problem(rng)

    np.testing.assert_allclose(
        ps.fused_segment_sum(data, ids, n, mask=mask),
        seg.segment_sum(data, ids, n, mask=mask),
        rtol=_RTOL, atol=_ATOL,
    )
    np.testing.assert_allclose(
        ps.fused_segment_mean(data, ids, n, mask=mask),
        seg.segment_mean(data, ids, n, mask=mask),
        rtol=_RTOL, atol=_ATOL,
    )

    # 3-D (GAT multi-head messages [E, h, f]); no mask.
    d3 = jnp.asarray(rng.normal(size=(64, 3, 5)).astype(np.float32))
    ids3 = jnp.asarray(rng.integers(0, 10, size=64).astype(np.int32))
    np.testing.assert_allclose(
        ps.fused_segment_sum(d3, ids3, 10),
        seg.segment_sum(d3, ids3, 10),
        rtol=_RTOL, atol=_ATOL,
    )

    # bf16 in → bf16 out (mixed-precision dtype flow preserved).
    dbf = data.astype(jnp.bfloat16)
    out = ps.fused_segment_sum(dbf, ids, n, mask=mask)
    assert out.dtype == jnp.bfloat16

    # Gradients flow (gather backward), masked rows get zero cotangent.
    g = jax.grad(lambda d: ps.fused_segment_sum(d, ids, n, mask=mask).sum())(data)
    g_ref = jax.grad(lambda d: seg.segment_sum(d, ids, n, mask=mask).sum())(data)
    np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-5)


def pytest_fused_segment_softmax_matches_xla(monkeypatch):
    """fused_segment_softmax (GATv2 attention path) == seg.segment_softmax —
    values and gradients, with masking."""
    monkeypatch.setenv("HYDRAGNN_PALLAS", "1")
    rng = np.random.default_rng(2)
    e, n, h = 200, 30, 6
    logits = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32) * 3)
    ids = jnp.asarray(rng.integers(0, n, size=e).astype(np.int32))
    mask = jnp.asarray(rng.random(e) > 0.25)

    a = ps.fused_segment_softmax(logits, ids, n, mask=mask)
    b = seg.segment_softmax(logits, ids, n, mask=mask)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert float(jnp.where(mask[:, None], a, 0.0).sum()) > 0
    assert not bool(jnp.any(jnp.where(~mask[:, None], a, 0.0) != 0))

    ga = jax.grad(lambda l: (ps.fused_segment_softmax(l, ids, n, mask=mask) ** 2).sum())(logits)
    gb = jax.grad(lambda l: (seg.segment_softmax(l, ids, n, mask=mask) ** 2).sum())(logits)
    np.testing.assert_allclose(ga, gb, rtol=1e-4, atol=1e-6)


def pytest_fused_ops_differentiable_under_shard_map(monkeypatch):
    """Graph-parallel backward through the fused kernels: grad must flow
    through shard_map over a 'graph' axis (regression: a zero-size dtype
    carrier in segment_sum_count's residuals picked up an inconsistent XLA
    sharding and crashed the backward)."""
    monkeypatch.setenv("HYDRAGNN_PALLAS", "1")
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("graph",))
    e, n, h = 64, 10, 3
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(e, h)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, n, size=e).astype(np.int32))

    def local(l_, ids_):
        s, c = ps.fused_segment_sum_count(l_, ids_, n, axis_name="graph")
        a = ps.fused_segment_softmax(l_, ids_, n, axis_name="graph")
        m = ps.fused_segment_mean(l_, ids_, n, axis_name="graph")
        return jax.lax.psum((s ** 2).sum() + (a ** 2).sum() + (m ** 2).sum(), "graph")

    f = jax.shard_map(
        local, mesh=mesh, in_specs=(P("graph"), P("graph")), out_specs=P(),
        check_vma=False,
    )
    g = jax.grad(lambda l: f(l, ids))(logits)
    assert bool(jnp.all(jnp.isfinite(g)))


def pytest_packed_split_boundary_matches_unpacked():
    """The f-packed split path (2f <= 128: hi/lo share one 128-lane tile and
    one matmul) must agree with the two-matmul split path across the packing
    boundary — f=64 packs, f=65 cannot."""
    rng = np.random.default_rng(11)
    for f in (1, 64, 65, 128):
        data = jnp.asarray(rng.normal(size=(300, f)).astype(np.float32) * 3.0)
        ids = jnp.asarray(rng.integers(0, 40, size=300).astype(np.int32))
        s_split, c_split = ps.segment_sum_count(data, ids, 40, True, split=True)
        ref = seg.segment_sum(data, ids, 40)
        # The split path rounds lo to bf16 (hardware-faithful), so the
        # interpreter shows the genuine bf16x2 error here too — same bound
        # as the rest of the file.
        np.testing.assert_allclose(s_split, ref, rtol=_RTOL, atol=_ATOL)
        np.testing.assert_allclose(c_split, seg.segment_count(ids, 40), rtol=1e-6)


def pytest_be_override_parity(monkeypatch):
    """HYDRAGNN_PALLAS_BE resizes the kernel's edge block at import time
    (benchmarks/tune_kernel.py sweeps it on hardware); any multiple of 128
    must give identical results."""
    import importlib

    rng = np.random.default_rng(13)
    data = jnp.asarray(rng.normal(size=(700, 9)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 50, size=700).astype(np.int32))
    want = seg.segment_sum(data, ids, 50)

    import os

    ambient = os.environ.get("HYDRAGNN_PALLAS_BE")
    monkeypatch.setenv("HYDRAGNN_PALLAS_BE", "256")
    importlib.reload(ps)
    try:
        assert ps._BE == 256
        s, c = ps.segment_sum_count(data, ids, 50, True)
        np.testing.assert_allclose(s, want, rtol=_RTOL, atol=_ATOL)
        np.testing.assert_allclose(c, seg.segment_count(ids, 50), rtol=1e-6)
    finally:
        # Restore the AMBIENT env (monkeypatch teardown will do the same for
        # os.environ — the reload must happen under that value or module
        # state and environment diverge for the rest of the session).
        if ambient is None:
            monkeypatch.delenv("HYDRAGNN_PALLAS_BE")
        else:
            monkeypatch.setenv("HYDRAGNN_PALLAS_BE", ambient)
        importlib.reload(ps)
    assert ps._BE == (int(ambient) if ambient else 512)


def pytest_block_skip_variant_matches_xla(monkeypatch):
    """HYDRAGNN_PALLAS_SKIP=1 predicates away non-overlapping (node-block,
    edge-block) pairs via scalar-prefetched receiver ranges and clamps their
    DMA index; results must be EXACTLY the regular kernel's on multi-block
    problems — contiguous (collation-like), scattered, and masked ids."""
    rng = np.random.default_rng(17)
    e, n, f = 1400, 300, 10  # >2 edge blocks, >2 node blocks

    # Collation-like contiguous receivers (ascending), plus scattered ids.
    contiguous = jnp.asarray(np.sort(rng.integers(0, n, size=e)).astype(np.int32))
    scattered = jnp.asarray(rng.integers(0, n, size=e).astype(np.int32))
    data = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32) * 2.0)
    mask = jnp.asarray(rng.random(e) > 0.2)

    for ids in (contiguous, scattered):
        masked_ids = jnp.where(mask, ids, -1)
        # The reference arm must run WITHOUT skip even if the ambient env
        # enables it (e.g. while validating the variant on hardware).
        monkeypatch.delenv("HYDRAGNN_PALLAS_SKIP", raising=False)
        want_s, want_c = ps.segment_sum_count(data, masked_ids, n, True)
        monkeypatch.setenv("HYDRAGNN_PALLAS_SKIP", "1")
        got_s, got_c = ps.segment_sum_count(data, masked_ids, n, True)
        monkeypatch.delenv("HYDRAGNN_PALLAS_SKIP")
        np.testing.assert_allclose(got_s, want_s, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(got_c, want_c)

    # Gradients ride the same custom VJP (gather backward) either way.
    monkeypatch.setenv("HYDRAGNN_PALLAS_SKIP", "1")
    g = jax.grad(
        lambda d: ps.segment_sum_count(d, contiguous, n, True)[0].sum()
    )(data)
    monkeypatch.delenv("HYDRAGNN_PALLAS_SKIP")
    g_ref = jax.grad(
        lambda d: ps.segment_sum_count(d, contiguous, n, True)[0].sum()
    )(data)
    np.testing.assert_allclose(g, g_ref, rtol=1e-6, atol=1e-6)


def pytest_block_skip_full_stats_and_model_path(monkeypatch):
    """The skip variant must compose through fused_segment_stats (split +
    centered second pass) and the empty-segment edge case."""
    monkeypatch.setenv("HYDRAGNN_PALLAS_SKIP", "1")
    rng = np.random.default_rng(19)
    data, ids, mask, n = _random_problem(rng, e=900, n=200, f=6)
    total, mean, std, count = ps.fused_segment_stats(
        data, ids, n, mask=mask, interpret=True
    )
    np.testing.assert_allclose(
        total, seg.segment_sum(data, ids, n, mask=mask), rtol=_RTOL, atol=_ATOL
    )
    np.testing.assert_allclose(
        std, seg.segment_std(data, ids, n, mask=mask), rtol=_RTOL, atol=_ATOL
    )
    np.testing.assert_allclose(count, seg.segment_count(ids, n, mask=mask), rtol=1e-6)

    # All-masked input: every block is skipped; outputs must be exact zeros.
    s, c = ps.segment_sum_count(data, jnp.full((900,), -1, jnp.int32), n, True)
    np.testing.assert_array_equal(c, np.zeros(n))
    np.testing.assert_array_equal(s, np.zeros((n, 6)))


def pytest_interpreter_certification_is_hardware_faithful():
    """Regression for the r05 on-hardware certification failure (ok=false at
    every block size while the interpreter passed): DEFAULT-precision MXU
    dots truncate f32 operands to bf16 on the chip but not in the
    interpreter. Two fixes make the interpreter predictive: the lo residual
    is explicitly bf16-rounded before packing (so the dot is exact on both
    platforms), and the std's sum-of-squares pass takes the hi/lo split
    (single-pass bf16 squares carried ~8e-3 error — 16x the gate). With
    both, certification must pass in the interpreter on the same 5e-4 gate
    the hardware run enforces."""
    import pytest

    report = ps.certify_pallas(e=2048, f=24, n=256, reps=1, sorted_arm=False)
    if report["backend"] == "tpu":  # hardware suite (HYDRAGNN_TPU_TESTS=1):
        pytest.skip("interpreter semantics under test; TPU covered by "
                    "tests/test_pallas_tpu.py")
    assert report["ok"], report
