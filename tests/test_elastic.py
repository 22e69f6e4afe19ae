"""graftelastic — elastic data-parallel training (docs/DISTRIBUTED.md
"Elastic runbook"): membership/heartbeat tracking, the deterministic
re-shard (exactly-once per-epoch consumption, disjoint per-rank views across
N→M transitions), the world-transition protocol e2e on the loopback harness
(kill/shrink, join/grow with zero new compiles, kill-during-transition
incarnation contract), the hardened ProxyRendezvous wire paths, the
supervisor.json topology-consumption check, and the checkpoint world-handoff
assertions."""

import os
import sys
import threading

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hydragnn_tpu.checkpoint.format import CheckpointError
from hydragnn_tpu.checkpoint.io import (
    elastic_handoff_meta,
    verify_elastic_handoff,
)
from hydragnn_tpu.graphs import GraphSample
from hydragnn_tpu.models import create_model
from hydragnn_tpu.parallel import (
    ElasticConfig,
    ElasticError,
    ElasticEvent,
    ElasticSchedule,
    ElasticTrainer,
    LoopbackError,
    MembershipTracker,
    ProxyRendezvous,
    check_restart_topology,
    shard_schedule,
)
from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
from hydragnn_tpu.utils.optimizer import select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
}


def _dataset(rng, count=24, lo=4, hi=12):
    graphs = []
    for _ in range(count):
        n = int(rng.integers(lo, hi))
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        graphs.append(
            GraphSample(
                x=x, pos=np.zeros((n, 3), np.float32),
                y=np.array([x.sum()], np.float32),
                y_loc=np.array([[0, 1]], np.int64), edge_index=ei,
            )
        )
    return graphs


def _loader(seed=0, count=24):
    loader = GraphDataLoader(
        _dataset(np.random.default_rng(seed), count=count),
        batch_size=4, shuffle=True, seed=seed,
    )
    loader.set_head_spec(("graph",), (1,))
    return loader


def _trainer(tmp_path, store=None, seed=0, max_workers=2, ckpt_every=2):
    loader = _loader(seed=seed)
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    opt = select_optimizer("AdamW", 5e-3)
    return ElasticTrainer(
        model, opt, loader,
        ElasticConfig(min_workers=1, max_workers=max_workers, heartbeat_s=5.0),
        run_path=str(tmp_path),
        compile_cache=store,
        checkpoint_every_steps=ckpt_every,
        seed=seed,
    )


# ---------------------------------------------------------------- membership
def pytest_elastic_config_parsing_and_admits():
    cfg = ElasticConfig.from_training(
        {"elastic": {"min_workers": 2, "max_workers": 4, "heartbeat_s": 1.5}}
    )
    assert (cfg.min_workers, cfg.max_workers, cfg.heartbeat_s) == (2, 4, 1.5)
    assert cfg.admits(2) and cfg.admits(4)
    assert not cfg.admits(1) and not cfg.admits(5)
    assert ElasticConfig.from_training({}) is None
    assert ElasticConfig.from_training(None) is None
    with pytest.raises(ValueError, match="unsatisfiable"):
        ElasticConfig(min_workers=3, max_workers=1)
    with pytest.raises(ValueError, match="positive"):
        ElasticConfig(heartbeat_s=0)


def pytest_membership_tracker_deadline_death_join_leave():
    """Death = a beat older than heartbeat_s (fake clock — no sleeps);
    joins/leaves are announcements consumed exactly once."""
    now = [0.0]
    tracker = MembershipTracker(heartbeat_s=1.0, clock=lambda: now[0])
    tracker.join("a")
    tracker.join("b")
    assert not tracker.poll(["a", "b"])  # both fresh, no pending changes
    now[0] = 0.9
    tracker.heartbeat("a")  # b's beat is now 0.9 old — still within deadline
    assert not tracker.poll(["a", "b"])
    now[0] = 1.95  # b last beat 0.0 -> 1.95 old; a 0.9 -> 1.05 old: BOTH dead
    tracker.heartbeat("a")  # a beats again just in time
    change = tracker.poll(["a", "b"])
    assert change.dead == ("b",) and not change.left and not change.joined
    assert not tracker.poll(["a"])  # the death was consumed
    # Clean leave + a new arrival, one poll each.
    tracker.request_leave("a")
    tracker.join("c")
    change = tracker.poll(["a"])
    assert change.left == ("a",) and change.joined == ("c",)
    assert not tracker.poll(["c"])  # consumed; c's stale join never resurfaces
    # mark_dead is immediate (the rendezvous-abort fast path).
    tracker.join("d")
    tracker.mark_dead("d")
    assert tracker.poll(["d"]).dead == ("d",)


def pytest_membership_tracker_drains_rendezvous_posts():
    from hydragnn_tpu.parallel import LoopbackRendezvous

    now = [0.0]
    tracker = MembershipTracker(heartbeat_s=1.0, clock=lambda: now[0])
    rdv = LoopbackRendezvous(2)
    rdv.post(0, {"wid": "w0"}, tag="heartbeat")
    rdv.post(1, {"wid": "w1"}, tag="heartbeat")
    rdv.post(1, "not-a-dict", tag="heartbeat")
    assert tracker.drain(rdv.posts("heartbeat")) == 2
    assert rdv.posts("heartbeat") == []  # drained
    assert tracker.alive() == {"w0", "w1"}


# ------------------------------------------------------- deterministic re-shard
def pytest_shard_schedule_exactly_once_and_disjoint_across_transition():
    """The conservation contract at the schedule level: a world transition at
    ANY cursor consumes every batch exactly once per epoch, and per-step
    rank views are disjoint."""
    num_batches = 11
    for world_a, world_b, switch_at in [(3, 2, 1), (2, 4, 2), (4, 1, 0)]:
        consumed = []
        steps_a = shard_schedule(num_batches, 0, world_a)[:switch_at]
        for step in steps_a:
            live = [i for i in step if i is not None]
            assert len(set(live)) == len(live)  # disjoint within the step
            consumed.extend(live)
        cursor = len(consumed)
        for step in shard_schedule(num_batches, cursor, world_b):
            live = [i for i in step if i is not None]
            assert len(set(live)) == len(live)
            consumed.extend(live)
        assert sorted(consumed) == list(range(num_batches)), (
            world_a, world_b, switch_at,
        )
    with pytest.raises(ValueError):
        shard_schedule(4, 0, 0)


def pytest_loader_reshard_across_checkpoint_boundary_preserves_multiset():
    """Satellite: same seed, N→M workers across a checkpoint boundary — the
    epoch's SAMPLE multiset is preserved and per-rank views are disjoint.
    The global plan comes from the unsharded loader (the elastic shard
    authority); the transition splits it at the handoff cursor."""
    loader = _loader(seed=3)
    loader.set_epoch(1)
    plan = loader._batch_plan()
    all_samples = sorted(
        int(i) for _pos, _bi, members, _need in plan for i in members
    )
    assert all_samples == sorted(range(len(loader.dataset)))  # sanity
    for n_workers, m_workers in [(2, 1), (1, 2), (3, 2)]:
        seen = []
        steps = shard_schedule(len(plan), 0, n_workers)[:2]
        for step in steps:
            rank_views = [
                set(int(s) for s in plan[i][2])
                for i in step
                if i is not None
            ]
            for a in range(len(rank_views)):
                for b in range(a + 1, len(rank_views)):
                    assert not (rank_views[a] & rank_views[b])  # disjoint
            seen.extend(s for view in rank_views for s in view)
        cursor = sum(
            1 for step in steps for i in step if i is not None
        )
        for step in shard_schedule(len(plan), cursor, m_workers):
            for i in step:
                if i is not None:
                    seen.extend(int(s) for s in plan[i][2])
        assert sorted(seen) == all_samples, (n_workers, m_workers)


# -------------------------------------------------------------- trainer e2e
def pytest_elastic_kill_shrinks_and_resumes_from_last_checkpoint(tmp_path):
    """Drill 1 shape, tier-1 size: a dirty worker death mid-epoch shrinks
    the world below the corpse and resumes from the LAST CHECKPOINT — the
    resumed (epoch, cursor) is a checkpointed position (zero lost progress
    beyond it), conservation holds, the run completes finite."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device (virtual) mesh")
    trainer = _trainer(tmp_path)
    report = trainer.run(
        num_epochs=2, start_world=2,
        schedule=ElasticSchedule(
            [ElasticEvent(step=3, kind="kill", worker="w1")]
        ),
    )
    assert report["completed"]
    shrinks = [
        t for t in report["transitions"]
        if t["kind"] == "shrink" and t["reason"] == "worker_death"
    ]
    assert len(shrinks) == 1
    assert (shrinks[0]["from_world"], shrinks[0]["to_world"]) == (2, 1)
    saved = [(s["epoch"], s["cursor"]) for s in report["save_log"]]
    assert (shrinks[0]["epoch"], shrinks[0]["cursor"]) in saved
    assert report["epoch_conservation_ok"]
    assert np.isfinite(report["final_eval_loss"])
    assert report["final_world"] == 1
    # The dirty shrink fires the elastic_transition flight dump into the run
    # dir, schema-valid (docs/OBSERVABILITY.md trigger table).
    import glob

    from hydragnn_tpu.telemetry.export import validate_flight_file

    dumps = glob.glob(
        str(tmp_path / "elastic" / "flightrec_*_elastic_transition.json")
    )
    assert dumps, "dirty shrink must dump the flight ring"
    assert validate_flight_file(dumps[0]) == []


def pytest_elastic_join_grows_rehydrating_zero_compiles(tmp_path):
    """Drill 2 shape: a clean leave then a join — the loader re-shards, the
    grow returns to a previously-seen topology, and its segment performs
    ZERO XLA compiles (the mesh-keyed executable hydrates — graftcache's
    warmup_xla_compiles=0 contract at a changed world size)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device (virtual) mesh")
    trainer = _trainer(tmp_path, store=str(tmp_path / "store"))
    report = trainer.run(
        num_epochs=2, start_world=2,
        schedule=ElasticSchedule(
            [
                ElasticEvent(step=2, kind="leave", worker="w1"),
                ElasticEvent(step=5, kind="join"),
            ]
        ),
    )
    assert report["completed"]
    grows = [t for t in report["transitions"] if t["kind"] == "grow"]
    assert len(grows) == 1
    assert (grows[0]["from_world"], grows[0]["to_world"]) == (1, 2)
    w2_segments = [s for s in report["segment_log"] if s["world"] == 2]
    assert len(w2_segments) >= 2
    assert w2_segments[-1]["compiles"] == 0, w2_segments
    assert report["epoch_conservation_ok"]
    assert report["final_world"] == 2


def pytest_elastic_kill_during_transition_incarnation_contract(tmp_path):
    """Drill 4 shape: a transition dies AFTER its handoff checkpoint — the
    next incarnation restores the exact saved position (atomic install ==
    never-torn state) and the run completes."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device (virtual) mesh")
    trainer = _trainer(tmp_path)
    report = trainer.run(
        num_epochs=2, start_world=2,
        schedule=ElasticSchedule(
            [
                ElasticEvent(step=3, kind="leave", worker="w1"),
                ElasticEvent(step=3, kind="kill_transition"),
            ]
        ),
    )
    assert report["completed"]
    assert report["incarnations"] == 1
    shrinks = [t for t in report["transitions"] if t["kind"] == "shrink"]
    assert shrinks and shrinks[0]["incarnation"] == 1
    saved = [(s["epoch"], s["cursor"]) for s in report["save_log"]]
    assert (shrinks[0]["epoch"], shrinks[0]["cursor"]) in saved
    assert report["epoch_conservation_ok"]


def pytest_elastic_same_quiesce_leave_plus_join_is_a_resize(tmp_path):
    """A leave and a join in the SAME quiesce at a full roster is a net-zero
    'resize' replacement, not a refusal: admission runs against the
    post-leave roster, the world size is unchanged, and the transition entry
    and telemetry agree on the kind."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device (virtual) mesh")
    trainer = _trainer(tmp_path)  # max_workers=2: roster starts FULL
    report = trainer.run(
        num_epochs=1, start_world=2,
        schedule=ElasticSchedule(
            [
                ElasticEvent(step=2, kind="leave", worker="w1"),
                ElasticEvent(step=2, kind="join", worker="jx"),
            ]
        ),
    )
    assert report["completed"]
    resizes = [t for t in report["transitions"] if t["kind"] == "resize"]
    assert len(resizes) == 1
    assert (resizes[0]["from_world"], resizes[0]["to_world"]) == (2, 2)
    assert report["final_world"] == 2
    assert "jx" in report["roster"] and "w1" not in report["roster"]
    assert report["epoch_conservation_ok"]


def pytest_elastic_shrink_below_min_workers_dies_loudly(tmp_path):
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device (virtual) mesh")
    loader = _loader()
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    opt = select_optimizer("AdamW", 5e-3)
    trainer = ElasticTrainer(
        model, opt, loader,
        ElasticConfig(min_workers=2, max_workers=2, heartbeat_s=5.0),
        run_path=str(tmp_path),
    )
    with pytest.raises(ElasticError, match="min_workers"):
        trainer.run(
            num_epochs=1, start_world=2,
            schedule=ElasticSchedule(
                [ElasticEvent(step=1, kind="kill", worker="w1")]
            ),
        )


# ------------------------------------------------------ proxy wire hardening
def pytest_proxy_rendezvous_post_mailbox_and_drain():
    """The one-way TCP mailbox: posts ACK immediately (no barrier round) and
    drain returns exactly what was posted, once."""
    rdv = ProxyRendezvous(world_size=3, timeout_s=10.0)
    port = rdv.serve()
    addr = f"127.0.0.1:{port}"
    try:
        for r in range(3):
            ProxyRendezvous.post(
                addr, "heartbeat", r, {"wid": f"proc{r}"}, timeout_s=10.0
            )
        posts = sorted(rdv.posts("heartbeat"))
        assert [p[1]["wid"] for p in posts] == ["proc0", "proc1", "proc2"]
        assert rdv.posts("heartbeat") == []
        # Posts never count toward allgather rounds: a full barrier round
        # still works on the same coordinator afterwards.
        def fn(w):
            return ProxyRendezvous.allgather(
                addr, "round", w.rank, w.rank * 2, timeout_s=10.0
            )

        from hydragnn_tpu.parallel import run_workers

        assert run_workers(3, fn) == [[0, 2, 4]] * 3
    finally:
        rdv.close()


def pytest_proxy_rendezvous_partial_frame_is_loud():
    """A coordinator dying mid-frame must surface as a LOUD partial-frame
    LoopbackError, not a hang or a bare JSON crash."""
    import socket

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    done = threading.Event()

    def truncating_server():
        conn, _ = srv.accept()
        conn.recv(4096)
        conn.sendall(b'{"result": [1, 2')  # no newline: torn mid-frame
        conn.close()
        done.set()

    t = threading.Thread(target=truncating_server, daemon=True)
    t.start()
    try:
        with pytest.raises(LoopbackError, match="partial frame"):
            ProxyRendezvous.allgather(
                f"127.0.0.1:{port}", "x", 0, None, timeout_s=5.0,
                connect_retries=0,
            )
        assert done.wait(5.0)
    finally:
        srv.close()
        t.join(5.0)


def pytest_proxy_rendezvous_connect_retry_and_exhaustion():
    """Connect retries ride a capped backoff (the DeviceFeed transient
    policy on the wire): a coordinator that binds late is reached; a dead
    address fails loudly naming the attempt count."""
    import socket

    # Reserve a port, start the coordinator only after a delay.
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    rdv = ProxyRendezvous(world_size=1, timeout_s=10.0)

    def late_serve():
        import time

        time.sleep(0.15)
        rdv.serve(port=port)

    t = threading.Thread(target=late_serve, daemon=True)
    t.start()
    try:
        out = ProxyRendezvous.allgather(
            f"127.0.0.1:{port}", "late", 0, "hi", timeout_s=10.0,
            connect_retries=4,
        )
        assert out == ["hi"]
    finally:
        t.join(5.0)
        rdv.close()
    with pytest.raises(LoopbackError, match="connect .* failed after"):
        ProxyRendezvous.allgather(
            f"127.0.0.1:{port}", "dead", 0, None, timeout_s=2.0,
            connect_retries=1,
        )


# --------------------------------------------------- restart topology consume
def pytest_check_restart_topology_matrix():
    elastic = ElasticConfig(min_workers=1, max_workers=4)
    mesh = {"world_size": 2, "graph_axis": 1}
    # Same topology: no transition.
    assert check_restart_topology(mesh, 2, 1, elastic) is None
    assert check_restart_topology({}, 8, 3, None) is None  # no block
    # Elastic-admitted world change: a descriptor, not an error.
    tr = check_restart_topology(mesh, 1, 1, elastic)
    assert tr == {"kind": "shrink", "from_world": 2, "to_world": 1}
    tr = check_restart_topology(mesh, 4, 1, elastic)
    assert tr["kind"] == "grow"
    # Contradictions fail loudly with both topologies named.
    with pytest.raises(RuntimeError, match="world_size=2.*world_size=8"):
        check_restart_topology(mesh, 8, 1, elastic)  # beyond max_workers
    with pytest.raises(RuntimeError, match="not configured"):
        check_restart_topology(mesh, 1, 1, None)  # not elastic at all
    # graph_axis changes are NEVER elastic.
    with pytest.raises(RuntimeError, match="graph_axis=1.*graph_axis=2"):
        check_restart_topology(mesh, 2, 2, elastic)


def pytest_supervisor_restart_with_new_world(tmp_path, monkeypatch):
    """run_supervised re-reads the scheduler env each incarnation: an
    elastic-admitted world change is recorded as a transition (and the mesh
    block updates so children compare against the CURRENT world); a
    non-admitted one raises naming both worlds."""
    import json
    import subprocess

    import hydragnn_tpu.parallel.distributed as dist
    from hydragnn_tpu.faults.supervisor import run_supervised

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(REPO, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["NeuralNetwork"]["Training"]["elastic"] = {
        "min_workers": 1, "max_workers": 2, "heartbeat_s": 30.0,
    }

    worlds = iter([2, 2, 1])  # meta build, attempt 0, attempt 1

    monkeypatch.setattr(
        dist, "init_comm_size_and_rank",
        lambda: (next(worlds, 1), 0),
    )

    rcs = iter([1, 0])  # first child dies, the shrunken retry completes

    class _FakeProc:
        pid = 12345

        def __init__(self):
            self._rc = next(rcs)

        def poll(self):
            return self._rc

        def kill(self):
            pass

        def wait(self, timeout=None):
            return self._rc

    monkeypatch.setattr(subprocess, "Popen", lambda *a, **k: _FakeProc())
    monkeypatch.chdir(tmp_path)
    meta = run_supervised(config, max_restarts=2)
    assert meta["completed"]
    assert meta["mesh"]["world_size"] == 1  # updated to the current world
    assert meta["elastic_transitions"] == [
        {"attempt": 1, "from_world": 2, "to_world": 1, "kind": "shrink"}
    ]
    assert [a["world_size"] for a in meta["attempts"]] == [2, 1]


# -------------------------------------------------- checkpoint world handoff
def pytest_verify_elastic_handoff_matrix():
    meta = {
        "epoch": 3,
        "elastic": elastic_handoff_meta(
            world_size=4, epoch=3, cursor=5, incarnation=1,
            global_step=40, num_batches=8,
        ),
    }
    # Any world in range hands off, including CHANGED ones.
    for w in (1, 2, 4, 8):
        out = verify_elastic_handoff(meta, w, min_workers=1, max_workers=8)
        assert (out["epoch"], out["cursor"], out["world_size"]) == (3, 5, 4)
        assert out["global_step"] == 40
    # Range violations name the worlds.
    with pytest.raises(CheckpointError, match=r"outside the"):
        verify_elastic_handoff(meta, 9, min_workers=1, max_workers=8)
    with pytest.raises(CheckpointError, match="positive"):
        verify_elastic_handoff(meta, 0)
    # A plain (non-elastic) checkpoint hands off at the epoch boundary.
    out = verify_elastic_handoff({"epoch": 7}, 3, min_workers=1, max_workers=4)
    assert out == {
        "epoch": 7, "cursor": 0, "world_size": None, "global_step": None,
    }
    # Malformed/incoherent blocks are corruption-grade failures, both
    # worlds named.
    with pytest.raises(CheckpointError, match="malformed"):
        verify_elastic_handoff(
            {"elastic": {"world_size": 2}}, 2, min_workers=1, max_workers=4
        )
    bad = {
        "elastic": elastic_handoff_meta(
            world_size=2, epoch=0, cursor=9, incarnation=0,
            global_step=1, num_batches=4,
        )
    }
    with pytest.raises(CheckpointError, match="incoherent"):
        verify_elastic_handoff(bad, 2, min_workers=1, max_workers=4)
