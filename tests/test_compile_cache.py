"""graftcache — the persistent compiled-executable store (hydragnn_tpu/cache,
docs/COMPILE_CACHE.md) — tier-1, CPU.

Contracts covered:
  * CacheKey round-trip + digest stability, store put/get round-trip,
    manifest/ls/verify/gc and the CLI;
  * fingerprint-mismatch rejection: jax version, topology, config
    fingerprint, and the donation flag each force a MISS;
  * corrupted/truncated entries fall back to a fresh compile LOUDLY
    (FaultCounters ``exec_cache_corrupt``, quarantined file) — never a crash;
  * serve warmup hydration: a second engine over a warm store hydrates the
    whole ladder with ZERO XLA compiles (compile-count spy) and serves
    outputs BIT-exact against the cold engine's;
  * concurrent writers: two engines warming one store directory at once —
    both serve, the store verifies clean, a third consumer hydrates fully;
  * trainer dispatch: a fresh TrainingDriver over a warm store hydrates its
    epoch programs and trains loss-bit-identically to an uncached driver;
  * supervisor-restart e2e (slow): a kill@K supervised run's restart
    incarnation resumes with a warm store (hydration visible in the run's
    train_metrics.prom).
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge
from hydragnn_tpu.analysis.sentinel import compile_count
from hydragnn_tpu.cache import (
    CacheKey,
    ExecutableRegistry,
    ExecutableStore,
    environment_fingerprint,
    tree_signature,
)
from hydragnn_tpu.faults import FaultCounters
from hydragnn_tpu.graphs import collate_graphs
from hydragnn_tpu.models import init_model_variables
from hydragnn_tpu.serve import InferenceEngine

LADDER = [(64, 512), (128, 1024)]


def _tiny_engine(cache_dir, **options):
    """Smallest useful PNA engine (graph+node heads) — compiles in ~1 s per
    rung on CPU; the cache behavior under test is orchestration."""
    rng = np.random.default_rng(0)
    graphs = ge._make_graphs(6, rng)
    model = ge._build_model(hidden=4, layers=1)
    batch = collate_graphs(graphs[:2], ge.TYPES, ge.DIMS, edge_dim=1)
    variables = init_model_variables(model, batch)
    options.setdefault("max_batch_graphs", 4)
    options.setdefault("max_delay_ms", 10.0)
    options.setdefault("bucket_ladder", LADDER)
    return (
        InferenceEngine(
            model, variables, compile_cache=str(cache_dir), **options
        ),
        graphs,
    )


def _predict_bytes(engine, graphs):
    """Deterministic raw-output-bytes digest — the bit-exactness witness."""
    out = []
    for g in graphs[:3]:
        for heads in engine.predict([g]):
            out.extend(np.ascontiguousarray(a).tobytes() for a in heads)
    return b"".join(out)


# ------------------------------------------------------------- key + store
@pytest.mark.mpi_skip
def pytest_cache_key_roundtrip_digest_and_store_cli(tmp_path):
    key = CacheKey.for_environment(
        program="unit",
        config_fingerprint="cfg",
        flags=("guard", "donate"),
        bucket=(64, 512, 5),
        args_digest="sig",
    )
    # flags normalize sorted; json round-trip preserves identity + digest.
    assert key.flags == ("donate", "guard")
    assert CacheKey.from_json(key.to_json()) == key
    assert CacheKey.from_json(json.loads(json.dumps(key.to_json()))).digest() == key.digest()
    env = environment_fingerprint()
    assert key.backend == env["backend"] and key.topology == env["topology"]

    store = ExecutableStore(str(tmp_path))
    store.put(key, {"executable": b"payload", "trees": b"trees"}, "pjrt")
    sections, fmt = store.get(key)
    assert fmt == "pjrt" and sections["executable"] == b"payload"
    rows = store.ls()
    assert len(rows) == 1 and rows[0]["key"]["program"] == "unit"
    assert all(r["ok"] for r in store.verify())

    # CLI mirrors the checkpoint CLI (ls | verify | gc).
    from hydragnn_tpu.cache.__main__ import main as cache_cli

    assert cache_cli(["ls", str(tmp_path), "--json"]) == 0
    assert cache_cli(["verify", str(tmp_path)]) == 0
    # gc keep-last prunes to the newest entries and sweeps STALE litter
    # only: a fresh .tmp may be a live concurrent writer's in-flight
    # install and must survive the sweep.
    key2 = CacheKey.for_environment("unit2", "cfg")
    store.put(key2, {"executable": b"p2"}, "pjrt")
    (tmp_path / "old_junk.tmp").write_bytes(b"x")
    (tmp_path / "live_write.tmp").write_bytes(b"y")
    import time as _time

    aged = _time.time() - 7200
    os.utime(tmp_path / "old_junk.tmp", (aged, aged))
    assert cache_cli(["gc", str(tmp_path), "--keep-last", "1"]) == 0
    assert [r["key"]["program"] for r in store.ls()] == ["unit2"]
    assert sorted(p.name for p in tmp_path.glob("*.tmp")) == ["live_write.tmp"]


@pytest.mark.mpi_skip
def pytest_fingerprint_mismatch_forces_miss(tmp_path):
    """Every key component is load-bearing: a changed jax version, device
    topology, config fingerprint, donation flag or device reads as a MISS —
    the store can never hand a stale program to a changed environment."""
    store = ExecutableStore(str(tmp_path))
    env = environment_fingerprint()
    base = CacheKey.for_environment(
        "prog", "cfg", flags=("donate",), bucket=(64, 512, 5), env=env
    )
    store.put(base, {"executable": b"exe"}, "pjrt")
    assert store.get(base) is not None
    variants = [
        CacheKey.for_environment(
            "prog", "cfg", flags=("donate",), bucket=(64, 512, 5),
            env=dict(env, jax_version=env["jax_version"] + ".post1"),
        ),
        CacheKey.for_environment(
            "prog", "cfg", flags=("donate",), bucket=(64, 512, 5),
            env=dict(env, topology=env["topology"] + "|procs=8"),
        ),
        CacheKey.for_environment(
            "prog", "OTHER-CONFIG", flags=("donate",), bucket=(64, 512, 5),
            env=env,
        ),
        CacheKey.for_environment(  # donation flag dropped
            "prog", "cfg", flags=(), bucket=(64, 512, 5), env=env
        ),
        CacheKey.for_environment(  # different bucket shape
            "prog", "cfg", flags=("donate",), bucket=(128, 512, 5), env=env
        ),
        CacheKey.for_environment(  # another chip of the same host
            "prog", "cfg", flags=("donate",), bucket=(64, 512, 5), env=env,
            devices=(base.devices[0] + 1,),
        ),
    ]
    for variant in variants:
        assert variant.digest() != base.digest()
        assert store.get(variant) is None, variant


@pytest.mark.mpi_skip
def pytest_corrupt_and_truncated_entries_fall_back(tmp_path):
    """A damaged entry is a LOUD fresh-compile fallback: the fault counter
    increments, the file is quarantined, the caller still gets a working
    executable — and the follow-up store-back self-heals the entry."""
    import jax

    f = jax.jit(lambda x: x * 3.0)
    x = jax.device_put(np.ones((8,), np.float32))
    key = CacheKey.for_environment(
        "corrupt_unit", "cfg", args_digest=tree_signature((x,))
    )
    reg = ExecutableRegistry(ExecutableStore(str(tmp_path)), name="unit")
    _, outcome, _ = reg.lookup_or_compile(("k",), key, lambda: f.lower(x))
    assert outcome == "compiled"
    path = reg.store.entry_path(key)

    for damage in ("flip", "truncate"):
        blob = bytearray(open(path, "rb").read())
        if damage == "flip":
            blob[len(blob) // 2] ^= 0xFF
        else:
            blob = blob[: len(blob) // 3]
        with open(path, "wb") as fh:
            fh.write(bytes(blob))
        before = FaultCounters.snapshot().get("exec_cache_corrupt", 0)
        fresh = ExecutableRegistry(ExecutableStore(str(tmp_path)), name="unit2")
        exe, outcome, _ = fresh.lookup_or_compile(
            ("k",), key, lambda: f.lower(x)
        )
        assert outcome == "compiled", damage
        assert np.array_equal(np.asarray(exe(x)), np.asarray(x) * 3.0)
        assert FaultCounters.snapshot()["exec_cache_corrupt"] == before + 1
        # Quarantined aside + self-healed: the store verifies clean again.
        assert os.path.exists(path + ".corrupt") or not os.path.exists(path)
        assert all(r["ok"] for r in ExecutableStore(str(tmp_path)).verify())


# ------------------------------------------------------------------- serve
@pytest.mark.mpi_skip
def pytest_serve_warmup_hydrates_zero_compiles_bit_exact(tmp_path):
    """The replica-spin-up property: engine 2 over engine 1's store warms
    the whole ladder by HYDRATION — zero XLA compiles (the spy is the
    recompile sentinel's counter, which deserialization must not trip) —
    and serves bit-exact outputs."""
    cold, graphs = _tiny_engine(tmp_path, warmup=True)
    try:
        cold_bytes = _predict_bytes(cold, graphs)
        cold_snap = cold.metrics.snapshot()["bucket_cache"]
        assert cold_snap["misses"] == len(LADDER)
        assert cold_snap["hydrated"] == 0
    finally:
        cold.close()

    warm, graphs = _tiny_engine(tmp_path, warmup=False)
    try:
        c0 = compile_count()
        compiled = warm.warmup()
        assert compile_count() - c0 == 0, "hydration fired an XLA compile"
        assert compiled == 0  # nothing was compiled — everything hydrated
        snap = warm.metrics.snapshot()["bucket_cache"]
        assert snap["hydrated"] == len(LADDER) and snap["misses"] == 0
        assert snap["hydrate_seconds"] >= 0.0
        assert warm.compiled_buckets == len(LADDER)
        assert _predict_bytes(warm, graphs) == cold_bytes
        assert warm.metrics.snapshot()["bucket_cache"]["misses"] == 0
        prom = warm.metrics.render_prometheus()
        assert "hydragnn_serve_exec_cache_hydrated_total 2" in prom
    finally:
        warm.close()


@pytest.mark.mpi_skip
def pytest_concurrent_writers_share_one_store(tmp_path):
    """Two engines, one store directory, warmed concurrently (the
    two-replicas-one-store topology): both serve, the store verifies clean,
    and a third consumer hydrates the full ladder."""
    results = {}

    def build(wid):
        engine, graphs = _tiny_engine(tmp_path, warmup=True)
        try:
            results[wid] = _predict_bytes(engine, graphs)
        finally:
            engine.close()

    threads = [
        threading.Thread(target=build, args=(w,), daemon=True)
        for w in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    assert set(results) == {0, 1}
    assert results[0] == results[1]
    store = ExecutableStore(str(tmp_path))
    reports = store.verify()
    assert reports and all(r["ok"] for r in reports)

    third, graphs = _tiny_engine(tmp_path, warmup=True)
    try:
        snap = third.metrics.snapshot()["bucket_cache"]
        assert snap["hydrated"] == len(LADDER) and snap["misses"] == 0
        assert _predict_bytes(third, graphs) == results[0]
    finally:
        third.close()


# ----------------------------------------------------------------- trainer
@pytest.mark.mpi_skip
def pytest_trainer_dispatch_hydrates_bit_exact(tmp_path):
    """The trainer's registry dispatch: (a) cache-enabled training is
    loss-bit-identical to the uncached jit path; (b) a FRESH driver over the
    warm store hydrates its epoch programs (cache/hydrate counters move,
    cache/miss does not) and converges identically."""
    from hydragnn_tpu import telemetry
    from hydragnn_tpu.graphs import GraphSample
    from hydragnn_tpu.models import create_model
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
    from hydragnn_tpu.train.train_validate_test import TrainingDriver
    from hydragnn_tpu.train.trainer import create_train_state
    from hydragnn_tpu.utils.optimizer import select_optimizer

    heads = {
        "graph": {
            "num_sharedlayers": 1,
            "dim_sharedlayers": 4,
            "num_headlayers": 1,
            "dim_headlayers": [4],
        },
    }

    def dataset(count=20):
        rng = np.random.default_rng(0)
        graphs = []
        for _ in range(count):
            n = int(rng.integers(4, 10))
            x = rng.normal(size=(n, 1)).astype(np.float32)
            ei = np.stack(
                [np.arange(n), (np.arange(n) + 1) % n]
            ).astype(np.int32)
            graphs.append(
                GraphSample(
                    x=x,
                    pos=np.zeros((n, 3), np.float32),
                    y=np.array([x.sum()], np.float32),
                    y_loc=np.array([[0, 1]], np.int64),
                    edge_index=ei,
                )
            )
        return graphs

    def run_epochs(cache_dir, epochs=2):
        loader = GraphDataLoader(dataset(), batch_size=5, shuffle=True)
        loader.set_head_spec(("graph",), (1,))
        model = create_model("SAGE", 1, 8, (1,), ("graph",), heads, [1.0], 2)
        variables = init_model_variables(model, next(iter(loader)))
        opt = select_optimizer("AdamW", 5e-3)
        state = create_train_state(model, variables, opt)
        driver = TrainingDriver(
            model, opt, state, compile_cache=cache_dir,
            compile_cache_fingerprint="unit-cfg",
        )
        losses = []
        for epoch in range(epochs):
            loader.set_epoch(epoch)
            losses.append(driver.train_epoch(loader)[0])
        return losses

    baseline = run_epochs(None)  # plain jit path (registry disabled)
    snap0 = telemetry.counters_snapshot("cache/")
    cached = run_epochs(str(tmp_path))  # cold store: compiles + stores
    assert cached == baseline, "registry dispatch changed the trajectory"
    snap1 = telemetry.counters_snapshot("cache/")
    assert snap1.get("cache/miss", 0) > snap0.get("cache/miss", 0)
    assert snap1.get("cache/store", 0) > snap0.get("cache/store", 0)

    warm = run_epochs(str(tmp_path))  # fresh driver, warm store: hydrates
    assert warm == baseline
    snap2 = telemetry.counters_snapshot("cache/")
    assert snap2.get("cache/hydrate", 0) > snap1.get("cache/hydrate", 0)
    assert snap2.get("cache/miss", 0) == snap1.get("cache/miss", 0), (
        "warm driver recompiled instead of hydrating"
    )


# ------------------------------------------------------- supervisor restart
@pytest.mark.mpi_skip
@pytest.mark.slow
def pytest_supervisor_restart_resumes_with_warm_store(tmp_path, monkeypatch):
    """E2E: a supervised run killed mid-training (kill@2) restarts and
    resumes with a WARM executable store — the restart incarnation hydrates
    instead of recompiling (visible in its train_metrics.prom), which is the
    seconds-not-minutes restart property ROADMAP item 3 names."""
    import signal

    from hydragnn_tpu.run_training import run_training
    from hydragnn_tpu.utils.config_utils import get_log_name_config
    from tests.deterministic_graph_data import deterministic_graph_data

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("HYDRAGNN_FAULTS", "kill@2")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["Visualization"] = {"create_plots": False}
    tr = config["NeuralNetwork"]["Training"]
    tr["num_epoch"] = 4
    tr["periodic_checkpoint_every"] = 1
    for split, cnt in {"train": 24, "test": 8, "validate": 8}.items():
        p = f"dataset/unit_test_singlehead_{split}"
        os.makedirs(p, exist_ok=True)
        deterministic_graph_data(p, number_configurations=cnt)
        config["Dataset"]["path"][split] = p

    meta = run_training(dict(config), supervise=True, max_restarts=2)
    assert meta["completed"] is True and meta["restarts"] == 1
    assert meta["attempts"][0]["returncode"] == -signal.SIGKILL

    log_name = get_log_name_config(config)
    # The supervisor defaulted the store on (supervised restarts are the
    # cold-start cost it amortizes) and incarnation 0 populated it.
    cache_dir = tmp_path / "logs" / log_name / "compile_cache"
    from hydragnn_tpu.cache.store import ENTRY_SUFFIX

    assert cache_dir.is_dir()
    assert any(f.suffix == ENTRY_SUFFIX for f in cache_dir.iterdir())
    # The final (restart) incarnation's metric dump shows hydration, not
    # recompilation, for the epoch programs.
    prom = (tmp_path / "logs" / log_name / "train_metrics.prom").read_text()
    hydrates = [
        float(line.split()[-1])
        for line in prom.splitlines()
        if line.startswith("hydragnn_cache_hydrate_total")
    ]
    assert hydrates and hydrates[0] > 0, prom[:2000]


# ------------------------------------------ hydration lands on the right devices
@pytest.mark.mpi_skip
def pytest_hydrated_executable_runs_on_the_devices_it_was_compiled_for(tmp_path):
    """On a multi-device host (the 8-device virtual CPU host here; four chips
    under HYDRAGNN_TPU_TESTS=1): a program on the default device, a mesh over
    every device and a mesh over a strict subset each come back from the
    store onto exactly the devices their key names, and run — the default of
    deserialize_and_load loads them onto ALL devices and dies at the first
    call asking for a shard per device. Keys that differ only in devices are
    different entries. A single-device program on a NON-default device is
    compiled every time and never stored — no entry, no quarantine, no
    corrupt count: the TPU runtime hands such a program back assigned to
    chip 0 (all four cases seen on the four-chip host, PR 21)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs a host with at least 4 devices")
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    expect = np.arange(8.0, dtype=np.float32) * 2 + 1
    store_dir = str(tmp_path / "store")
    corrupt_before = FaultCounters.snapshot().get("exec_cache_corrupt", 0)

    def lookup(group):
        if len(group) == 1:
            x = jax.device_put(jnp.arange(8.0), group[0])
        else:
            mesh = Mesh(np.array(group), ("data",))
            x = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P("data")))
        key = CacheKey.for_environment(
            "prog", "fp", devices=tuple(d.id for d in group)
        )
        reg = ExecutableRegistry(ExecutableStore(store_dir))  # a new process
        exe, outcome, _s = reg.lookup_or_compile("k", key, lambda: fn.lower(x))
        out = exe(x)
        np.testing.assert_array_equal(np.asarray(out), expect)
        assert out.sharding.device_set == set(group)
        return outcome

    groups = (devices[:1], devices, devices[1 : 1 + len(devices) // 2])
    assert [lookup(g) for g in groups] == ["compiled"] * 3
    assert [lookup(g) for g in groups] == ["disk"] * 3
    assert len(ExecutableStore(store_dir).ls()) == 3

    assert [lookup(devices[-1:]) for _ in range(2)] == ["compiled"] * 2
    assert len(ExecutableStore(store_dir).ls()) == 3
    assert not [f for f in os.listdir(store_dir) if f.endswith(".corrupt")]
    assert (
        FaultCounters.snapshot().get("exec_cache_corrupt", 0) == corrupt_before
    )


@pytest.mark.mpi_skip
def pytest_program_keyed_for_the_wrong_devices_is_not_stored(tmp_path):
    """The key's devices are the caller's word; the executable knows where it
    was built. A disagreement is a warning and no entry — stored, it would
    later be loaded onto devices it was not compiled for."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs a host with at least 2 devices")
    x = jax.device_put(jnp.arange(8.0), devices[1])
    reg = ExecutableRegistry(ExecutableStore(str(tmp_path / "store")))
    key = CacheKey.for_environment("prog", "fp")  # says: the default device
    with pytest.warns(RuntimeWarning, match="not stored"):
        _exe, outcome, _s = reg.lookup_or_compile(
            "k", key, lambda: jax.jit(lambda v: v + 1).lower(x)
        )
    assert outcome == "compiled"
    assert ExecutableStore(str(tmp_path / "store")).ls() == []


# ------------------------------------------- where JAX's own cache is placed
@pytest.fixture
def _jax_cache_dir_restored():
    import jax

    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def pytest_jax_cache_env_set_program_sets_nothing(
    monkeypatch, tmp_path, _jax_cache_dir_restored
):
    import jax

    from hydragnn_tpu.cache.jaxcache import place_jax_cache

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert place_jax_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # untouched in code


def pytest_jax_cache_env_unset_one_fixed_path_in_the_checkout(
    monkeypatch, _jax_cache_dir_restored
):
    import subprocess

    import jax

    from hydragnn_tpu.cache.jaxcache import JAX_CACHE_DIR, place_jax_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert place_jax_cache() == JAX_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == JAX_CACHE_DIR
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=repo
    )
    if os.path.isdir(os.path.join(repo, ".git")):
        assert ignored.returncode == 0, ".jax_cache/ must be gitignored"


@pytest.mark.mpi_skip
def pytest_graftcache_never_repoints_jax_cache(
    monkeypatch, tmp_path, _jax_cache_dir_restored
):
    """A backend that cannot serialize executables makes the registry persist
    StableHLO only; it used to re-point jax_compilation_cache_dir under the
    store (a drill's TemporaryDirectory). It now leaves it alone."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.cache import registry as registry_mod

    monkeypatch.setattr(registry_mod, "serialize_compiled", lambda c: None)
    jax.config.update("jax_compilation_cache_dir", "/somewhere/fixed")
    reg = ExecutableRegistry(ExecutableStore(str(tmp_path / "store")))
    key = CacheKey.for_environment("prog", "fp")
    x = jnp.ones(4)
    with pytest.warns(RuntimeWarning, match="cannot serialize"):
        _exe, outcome, _s = reg.lookup_or_compile(
            "k", key, lambda: jax.jit(lambda v: v + 1).lower(x)
        )
    assert outcome == "compiled"
    assert jax.config.jax_compilation_cache_dir == "/somewhere/fixed"
    rows = ExecutableStore(str(tmp_path / "store")).ls()
    assert [r["exe_format"] for r in rows] == ["stablehlo"]
    assert not os.path.exists(tmp_path / "store" / "xla")
