"""``xplane_scopes.py`` on the two traces recorded on one TPU v5 lite chip:
``small_v5e.xplane.pb`` (PR 22, ``testdata/record.py``: one scope, no module)
and ``scoped_v5e.xplane.pb`` (PR 23, ``testdata/record_scoped.py``: a train
step under the program's scope vocabulary). What is asserted of the second
is what its script is known to have run."""

import os
import types

import pytest

from graftbench import flops, trace_reduce, xplane_scopes
from graftbench.layer_metrics import (
    agg_roofline_share, agg_step_ms, gather_roofline_share, gather_step_ms,
    model_dense_step_ms, optimizer_step_ms, scope_coverage,
)

DATA = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata"
)
SMALL = os.path.join(DATA, "small_v5e.xplane.pb")
SCOPED = os.path.join(DATA, "scoped_v5e.xplane.pb")


def pytest_reader_finds_the_scope_profiledata_hides():
    (device,) = xplane_scopes.device_ops(xplane_scopes.parse(SMALL)).values()
    events, metadata = device
    convs = {
        m: meta for m, meta in metadata.items()
        if meta["stats"].get("hlo_category") == "convolution fusion"
    }
    assert len(convs) == 4
    for meta in convs.values():
        assert meta["stats"]["tf_op"] == "jit(step)/hydragnn.train_step/dot_general:"
        assert meta["stats"]["source"].endswith("graftbench/testdata/record.py:23")
    by_name = {meta["display_name"]: meta["stats"] for meta in convs.values()}
    assert by_name["fusion.3"]["flops"] == 17188257792
    assert by_name["fusion.3"]["bytes_accessed"] == 41943040
    assert len({meta["stats"]["program_id"] for meta in convs.values()}) == 1
    assert len(events) == 18  # the XLA Ops line: 6 an execution, 3 executions


@pytest.mark.parametrize("use_window", [True, False])
def pytest_window_and_busy_equal_trace_reduce(use_window):
    ours = xplane_scopes.by_scope(SMALL, use_window=use_window)
    theirs = trace_reduce.reduce(
        trace_reduce.load(SMALL), ("device_step",), use_window=use_window
    )
    assert ours["chips"] == theirs["chips"] == 1
    if use_window:  # without one, theirs spans the programs too, ours the ops
        assert ours["window_s"] == pytest.approx(theirs["window_s"], abs=1e-9)
    assert ours["busy_s"] == pytest.approx(theirs["busy_s"], abs=1e-9)
    # Self times add up to the busy time: the ops line is one stream.
    assert ours["device_self_s"] == pytest.approx(ours["busy_s"], abs=1e-9)
    # One root scope and no module: rooted, and not covered.
    assert {(r["root"], r["rooted"], r["module"], r["scope"]) for r in ours["rows"]} \
        == {("train", True, "-", "(none)"), ("train", False, "-", "(none)")}
    assert ours["coverage"] == 0.0
    assert ours["uncovered"][0][0] == "convolution fusion bf16[2048,2048]"


@pytest.mark.parametrize("tf_op, expected", [
    ("jit(step)/hydragnn.train_step/jvp(HydraGNN)/conv_1/hydragnn.agg.pna/"
     "hydragnn.agg.stats.csr/gather:",
     ("train", "fwd", "conv_1", "hydragnn.agg.stats.csr")),
    ("jit(step)/hydragnn.train_step/transpose(hydragnn.train_step)/jvp(HydraGNN)/"
     "conv_0/hydragnn.agg.pna/hydragnn.agg.extrema.xla/gather:",
     ("train", "bwd", "conv_0", "hydragnn.agg.extrema.xla")),
    ("jit(step)/hydragnn.train_step/transpose(jvp(HydraGNN))/conv_1/"
     "hydragnn.gather/scatter-add:",
     ("train", "bwd", "conv_1", "hydragnn.gather")),
    ("jit(epoch)/hydragnn.train_epoch_scan/while/body/closed_call/jvp(HydraGNN)/"
     "conv_0/pre_nn/dot_general:",
     ("train", "fwd", "conv_0", "(model)")),
    ("jit(step)/hydragnn.train_step/shard_map/transpose(jvp(HydraGNN))/"
     "jvp(HydraGNN)/checkpoint/rematted_computation/conv_0/hydragnn.gather/gather:",
     ("train", "bwd", "conv_0", "hydragnn.gather")),
    ("jit(step)/hydragnn.eval_step/shard_map/HydraGNN/checkpoint/conv_0/lin/"
     "dot_general:", ("eval", "fwd", "conv_0", "(model)")),
    ("jit(step)/hydragnn.train_step/jvp(HydraGNN)/hydragnn.pool/"
     "hydragnn.agg.mean.csr/div:",
     ("train", "fwd", "(model)", "hydragnn.agg.mean.csr")),
    ("jit(step)/hydragnn.train_step/jvp(HydraGNN)/hydragnn.pool/"
     "hydragnn.agg.mean.csr/_prefix_open/while/body/add:",
     ("train", "fwd", "(model)", "hydragnn.agg.mean.csr")),
    ("jit(step)/hydragnn.train_step/jvp(HydraGNN)/jit(relu)/max:",
     ("train", "fwd", "(model)", "(model)")),
    ("jit(step)/hydragnn.train_step/jvp(HydraGNN)/head_1/mlp/dense_0/dot_general:",
     ("train", "fwd", "head_1", "(model)")),
    ("jit(step)/hydragnn.train_step/transpose(jvp(hydragnn.loss))/div:",
     ("train", "bwd", "-", "hydragnn.loss")),
    ("jit(step)/hydragnn.train_step/hydragnn.optimizer/jit(_where)/select_n:",
     ("train", "fwd", "-", "hydragnn.optimizer")),
    ("jit(step)/hydragnn.train_step/add:", ("train", "fwd", "-", "(none)")),
    ("jit(_lambda)/mul:", (None, "fwd", "-", "(none)")),
    ("", (None, "fwd", "-", "(none)")),
])
def pytest_classify_paths_the_programs_compile_to(tf_op, expected):
    """The path forms are the ones ``tests/test_scopes.py`` sees compiled."""
    assert xplane_scopes.classify(tf_op) == expected


# --------------------------------------------------- the scoped recorded trace
@pytest.fixture(scope="module")
def scoped():
    return xplane_scopes.by_scope(SCOPED)


def _run(steps=3):
    reduced = trace_reduce.reduce(trace_reduce.load(SCOPED), ("device_step",))
    cell = types.SimpleNamespace(trace_dir=None, out_dir=None)
    return types.SimpleNamespace(cell=cell, facts={"steps": steps}, trace=reduced)


def pytest_scoped_rows_are_what_the_script_ran(scoped):
    rows = {
        (r["root"], r["direction"], r["module"], r["scope"]): r
        for r in scoped["rows"] if r["rooted"]
    }
    assert {k[0] for k in rows} == {"train"}
    # Forward: the row gather, the two Dense layers, the segment sum, the loss.
    assert ("train", "fwd", "conv_0", "hydragnn.gather") in rows
    assert ("train", "fwd", "conv_0", "hydragnn.agg.sum.xla") in rows
    assert ("train", "fwd", "conv_0", "(model)") in rows
    # Backward: the gather's scatter-add, the sum's gather, the Dense
    # layers' transposes, all inside transpose(...).
    assert ("train", "bwd", "conv_0", "hydragnn.gather") in rows
    assert ("train", "bwd", "conv_0", "hydragnn.agg.sum.xla") in rows
    assert ("train", "bwd", "conv_0", "(model)") in rows
    assert any(k[3] == "hydragnn.optimizer" and k[2] == "-" for k in rows)
    # The script's loss is under hydragnn.loss and no operation says so: XLA
    # fused it into the backward of ``post``, and a fusion has its root's
    # name alone. Attribution is by root.
    assert not any(k[3] == "hydragnn.loss" for k in rows)
    # Every name in the trace is a name of the program's vocabulary.
    from hydragnn_tpu.telemetry import scopes

    used = {k[3] for k in rows if k[3].startswith("hydragnn.")}
    assert used <= scopes.VOCABULARY
    # XLA's own figures ride along: the Dense layers have flops, the
    # gather's scatter-add moves bytes.
    assert rows[("train", "fwd", "conv_0", "(model)")]["flops"] > 0
    assert rows[("train", "bwd", "conv_0", "hydragnn.gather")]["bytes_accessed"] > 0
    assert scoped["coverage"] > 0.9
    assert scoped["device_self_s"] == pytest.approx(scoped["busy_s"], rel=1e-6)


def pytest_step_readers_add_up_to_device_step_ms(scoped, monkeypatch):
    """The four ``*_step_ms`` and the remainder are the train root's device
    time a step, and that is ``device_step_ms`` (device time of the programs
    under the host's ``device_step`` span) to within 2%."""
    run = _run()
    scoped = dict(scoped, step_ms=xplane_scopes.step_split(scoped, 3))
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: scoped)
    parts = {
        "agg": agg_step_ms.read(run), "gather": gather_step_ms.read(run),
        "model_dense": model_dense_step_ms.read(run),
        "optimizer": optimizer_step_ms.read(run),
    }
    assert all(v is not None and v > 0 for v in parts.values()), parts
    split = xplane_scopes.step_split(scoped, 3)
    for name, value in parts.items():
        assert value == pytest.approx(split[name])
    total = sum(parts.values()) + split["pool"] + split["unattributed"]
    assert total == pytest.approx(split["train_root"])
    by_span_ms = 1e3 * run.trace["by_span"]["device_step"]["seconds"] / 3
    assert total == pytest.approx(by_span_ms, rel=0.02)
    assert scope_coverage.read(run) == pytest.approx(100 * scoped["coverage"])


def pytest_roofline_readers_on_the_scoped_trace(scoped, monkeypatch):
    """The recorded step (``testdata/record_scoped.py``) gathers 65,536 rows
    of a learned [4096, 128] table and segment-sums 65,536 messages of 128
    into 4,096 rows, forward and backward: the counted bytes over the device
    time of each scope over the v5e's 819 GB/s."""
    n, e, f = 4096, 65536, 128
    counted = flops.total(
        [flops.gather(n, e, f), flops.segment_reduce(e, n, f)]
    )["bytes"]
    assert counted["gather"] == {"fwd": 4 * (2 * e * f + e), "bwd": 4 * (e * f + e + n * f)}
    assert counted["agg"] == {"fwd": 4 * (e * f + e + n * f), "bwd": 4 * (n * f + e + e * f)}
    run = _run()
    run.facts.update(step_bytes={k: counted[k] for k in ("gather", "agg")}, chips=1)
    run.peaks = {"hbm_bytes_per_s": 819e9}
    scoped = dict(scoped, step_ms=xplane_scopes.step_split(scoped, 3))
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: scoped)
    for reader, ms, scope in ((gather_roofline_share, gather_step_ms, "gather"),
                              (agg_roofline_share, agg_step_ms, "agg")):
        share = reader.read(run)
        gb_s = sum(counted[scope].values()) / (ms.read(run) * 1e-3) / 1e9
        assert share == pytest.approx(100 * gb_s / 819)
        assert 1.0 < share < 100.0, (scope, share)
    # The split ``scopes.json`` keeps: the same seconds and bytes by
    # direction, XLA's figure beside the counted one.
    split = xplane_scopes.bytes_split(scoped, run.facts)
    assert set(split) == {"gather", "agg"}
    for scope, directions in split.items():
        assert set(directions) == {"fwd", "bwd"}
        assert sum(d["ms"] for d in directions.values()) == pytest.approx(
            scoped["step_ms"][scope]
        )
        for direction, d in directions.items():
            assert d["counted_bytes"] == counted[scope][direction]
            assert d["xla_bytes"] > 0
            assert d["counted_gb_s"] == pytest.approx(
                d["counted_bytes"] / d["ms"] / 1e6
            )
    # Two chips: the counted bytes are all chips', the seconds a chip's mean.
    run.facts["chips"] = 2
    assert gather_roofline_share.read(run) == pytest.approx(
        50 * sum(counted["gather"].values()) / (gather_step_ms.read(run) * 1e-3) / 819e9
    )
    # No count (a driver that gives none), no reading.
    del run.facts["step_bytes"]
    assert gather_roofline_share.read(run) is None


def pytest_readers_return_nothing_without_scopes_or_trace(monkeypatch, tmp_path):
    """On a program that opens no leaf scope (the parent of PR 23) the agg
    and gather readers return None and nothing raises; with no trace at all
    every reader does."""
    small = xplane_scopes.by_scope(SMALL)
    small = dict(small, step_ms=xplane_scopes.step_split(small, 3))
    run = _run()
    monkeypatch.setattr(xplane_scopes, "table", lambda _run: small)
    run.facts["step_bytes"] = {"gather": {"fwd": 1, "bwd": 1}, "agg": {"fwd": 1, "bwd": 1}}
    run.peaks = {"hbm_bytes_per_s": 819e9}
    assert agg_step_ms.read(run) is None and agg_roofline_share.read(run) is None
    assert gather_step_ms.read(run) is None and gather_roofline_share.read(run) is None
    assert model_dense_step_ms.read(run) is None
    assert optimizer_step_ms.read(run) > 0  # rooted, no module
    assert scope_coverage.read(run) == 0.0
    monkeypatch.undo()
    run.cell.trace_dir = str(tmp_path)
    for reader in (agg_step_ms, gather_step_ms, model_dense_step_ms,
                   optimizer_step_ms, scope_coverage, agg_roofline_share,
                   gather_roofline_share):
        assert reader.read(run) is None


def pytest_table_writes_scopes_json_once(tmp_path):
    import json
    import shutil

    trace = tmp_path / "trace" / "plugins" / "profile" / "run"
    trace.mkdir(parents=True)
    shutil.copy(SCOPED, trace / "host.xplane.pb")
    run = _run()
    run.cell.trace_dir, run.cell.out_dir = str(tmp_path / "trace"), str(tmp_path)
    first = xplane_scopes.table(run)
    with open(tmp_path / "scopes.json") as f:
        written = json.load(f)
    assert written["steps"] == 3 and written["coverage"] == first["coverage"]
    assert written["step_ms"]["train_root"] > 0
    assert {"root", "direction", "module", "scope", "seconds", "flops",
            "bytes_accessed"} <= set(written["rows"][0])
    os.remove(tmp_path / "scopes.json")
    xplane_scopes.table(run)  # the other four readers: no second write
    assert not os.path.exists(tmp_path / "scopes.json")
