"""graftlint rule catalogue + the framework knowledge the rules key off.

Every rule guards an invariant this framework PAID to establish and that
nothing mechanical checked before this module existed (docs/STATIC_ANALYSIS.md
has the full catalogue with examples):

* ``host-sync-in-step``   — no host synchronization inside code reachable from
  a compiled step body (trainer._step_body, the scan/shard_map paths, the
  serve worker's jitted forward). A ``.item()`` / ``np.asarray`` / ``float()``
  on a traced value either fails at trace time or — worse — silently forces a
  device round-trip per step when the function also runs eagerly.
* ``cond-in-guard``       — the non-finite step guard must stay bit-inert:
  ``jnp.where`` selects, never ``lax.cond`` (a conditional region moves XLA's
  fusion boundaries; the clean path then stops being bit-identical to the
  unguarded build — measured, trainer._keep_if's docstring).
* ``use-after-donate``    — a buffer passed at a donated position of a
  ``donate_argnums`` callable is dead; reading it afterwards is undefined
  behavior that XLA only sometimes reports.
* ``recompile-hazard``    — patterns that silently multiply compiles:
  jnp work at module import time, jit-wrapper construction inside a loop,
  unhashable literals fed to static args.
* ``nondeterminism``      — wall-clock / global-RNG entropy in traced code or
  in the collation path (collation must be a pure function of (dataset, seed,
  epoch) for the resume/replay contracts to hold).

``suppression-without-reason`` is the meta-rule: every inline
``# graftlint: disable=<rule>(<reason>)`` must carry a justification string.

The ``graftrace`` half (analysis/concurrency.py) adds the host-concurrency
rules over the same catalogue — the five cooperating thread roots
(prefetch/transfer pipeline, serve batcher+dispatcher+HTTP handlers,
checkpoint writer, supervisor loop) share counters, caches, and manifests
that nothing mechanical checked before:

* ``missing-guard-decl``      — an attribute written from >= 2 thread roots
  carries no ``# guarded-by: <lock>`` declaration.
* ``unguarded-shared-write``  — a write to a guard-declared attribute outside
  a ``with <that lock>:`` block (never baselineable: a lost update corrupts
  counters/caches silently).
* ``guard-mismatch``          — an access to a guard-declared attribute under
  a different lock than declared, or an unlocked read without a
  ``dirty-reads`` clause in the declaration.
* ``lock-order-inversion``    — the static lock-order graph has a cycle
  (two threads can acquire the same pair of locks in opposite orders).
* ``blocking-queue-in-lock``  — an unbounded blocking operation
  (queue get/put/join, Event.wait, Thread.join) reachable while a lock is
  held: the classic convoy/deadlock shape.
* ``fork-after-threads``      — ``os.fork`` / fork-context multiprocessing in
  a package that starts threads (a forked child inherits locked locks).
* ``jax-dispatch-off-main``   — JAX dispatch from a thread root outside the
  sanctioned DeviceFeed transfer / serve dispatch paths.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Rule:
    id: str
    summary: str


RULES = {
    r.id: r
    for r in (
        Rule(
            "host-sync-in-step",
            "host-sync call (.item()/.tolist()/float()/np.asarray/"
            "jax.device_get/block_until_ready) in code reachable from a "
            "compiled step body",
        ),
        Rule(
            "cond-in-guard",
            "lax.cond/lax.switch or Python branching on the all-finite flag "
            "in guard-path code — the guard must stay bit-inert (jnp.where)",
        ),
        Rule(
            "use-after-donate",
            "read of a buffer after it was passed at a donated position of a "
            "donate_argnums callable",
        ),
        Rule(
            "recompile-hazard",
            "silent compile multiplier: jnp work at import time, jit "
            "construction inside a loop, unhashable static-arg literal",
        ),
        Rule(
            "nondeterminism",
            "wall-clock or global-RNG entropy in traced or "
            "collation-deterministic code",
        ),
        Rule(
            "suppression-without-reason",
            "graftlint suppression comment without a justification string",
        ),
        # ------------------------------------------------ graftrace (concurrency)
        Rule(
            "missing-guard-decl",
            "attribute written from >= 2 thread roots without a "
            "'# guarded-by: <lock>' declaration",
        ),
        Rule(
            "unguarded-shared-write",
            "write to a guard-declared shared attribute outside a "
            "'with <declared lock>:' block",
        ),
        Rule(
            "guard-mismatch",
            "access to a guard-declared attribute under the wrong lock, or "
            "an unlocked read without a dirty-reads clause",
        ),
        Rule(
            "lock-order-inversion",
            "cycle in the static lock-order graph (potential deadlock)",
        ),
        Rule(
            "blocking-queue-in-lock",
            "unbounded blocking operation (queue get/put/join, Event.wait, "
            "Thread.join) reachable while holding a lock",
        ),
        Rule(
            "fork-after-threads",
            "os.fork / fork-context multiprocessing in a thread-spawning "
            "package (forked children inherit held locks)",
        ),
        Rule(
            "jax-dispatch-off-main",
            "JAX dispatch from a thread root outside the sanctioned "
            "DeviceFeed transfer / serve dispatch paths",
        ),
        # ------------------------------------------------ graftproto (protocol)
        Rule(
            "collective-divergence",
            "rank-dependent branch, or a branch whose arms trace different "
            "collective sequences, inside compiled/lockstep code — ranks "
            "would issue mismatched collectives and the mesh deadlocks",
        ),
        Rule(
            "barrier-divergence",
            "members of one lockstep segment reach different named-barrier "
            "sequences — the rendezvous round can never complete",
        ),
        Rule(
            "barrier-under-lock",
            "rendezvous barrier reached while holding a lock another thread "
            "root acquires — a distributed convoy/deadlock shape",
        ),
        Rule(
            "leader-only-barrier",
            "rendezvous barrier inside a rank-guarded branch — followers "
            "never arrive and the leader blocks until the round times out",
        ),
        Rule(
            "torn-state-hazard",
            "persistence write in control-plane state code that is not "
            "atomic-rename-shaped (or a multi-file update without a single "
            "authoritative install) — a crash tears the recovered state",
        ),
        # ------------------------------------------------ graftlint additions
        Rule(
            "pickle-load-outside-compat",
            "pickle.load/pickle.loads/torch.load outside the sanctioned "
            "v1-compat shims — the raw-pickle read path was deprecated in "
            "PR 16 (GSHD convert CLI); new call sites are regressions",
        ),
    )
}

# Rule ids owned by the graftrace concurrency pass (analysis/concurrency.py);
# everything else in RULES is the graftlint pass's.
CONCURRENCY_RULES = frozenset(
    {
        "missing-guard-decl",
        "unguarded-shared-write",
        "guard-mismatch",
        "lock-order-inversion",
        "blocking-queue-in-lock",
        "fork-after-threads",
        "jax-dispatch-off-main",
    }
)

# Rule ids owned by the graftproto protocol pass (analysis/proto.py). The
# three passes (lint / trace / proto) partition RULES so their baseline
# updates never clobber each other's keys (__main__.py preserve logic).
PROTO_RULES = frozenset(
    {
        "collective-divergence",
        "barrier-divergence",
        "barrier-under-lock",
        "leader-only-barrier",
        "torn-state-hazard",
    }
)


# --------------------------------------------------------------- framework map
# Factories whose NESTED function definitions are compiled step bodies even
# though the jit/scan wrapping happens at the call site (trainer.py's
# ``_step_body`` returns ``body``; make_train_step jits it later). Static
# call-graph analysis cannot see through the closure return, so the linter is
# told directly.
TRACED_FACTORIES = frozenset(
    {
        "_step_body",
        "make_train_step",
        "make_eval_step",
        "make_train_epoch_scan",
        "make_train_step_dp",
        "make_eval_step_dp",
    }
)

# Callables that return a donating compiled step (donate_argnums=(0,)):
# calling one binds a callable whose argument 0 buffer set is consumed.
DONATING_FACTORIES = {
    "make_train_step": (0,),
    "make_train_step_dp": (0,),
    "make_train_epoch_scan": (0,),
}

# jax transforms whose callable arguments become traced roots.
TRANSFORM_ENTRY_POINTS = frozenset(
    {
        "jax.jit",
        "jit",
        "jax.pmap",
        "jax.vmap",
        "vmap",
        "jax.grad",
        "jax.value_and_grad",
        "jax.checkpoint",
        "jax.remat",
        "jax.eval_shape",
        "jax.lax.scan",
        "lax.scan",
        "jax.lax.while_loop",
        "lax.while_loop",
        "jax.lax.fori_loop",
        "lax.fori_loop",
        "jax.lax.cond",
        "lax.cond",
        "jax.lax.switch",
        "lax.switch",
        "shard_map",
        "jax.shard_map",
        "pl.pallas_call",
        "pallas_call",
    }
)

# Module-path substrings whose TRACED functions form the guard path — the
# bit-inertness invariant scope for ``cond-in-guard``.
GUARD_PATH_MODULES = ("train/trainer.py",)
# Functions that are guard-path regardless of module (helpers the guard owns).
GUARD_PATH_FUNCTIONS = frozenset({"_keep_if", "_all_finite"})

# Module-path substrings where collation/splitting determinism is contractual:
# batches must be a pure function of (dataset, seed, epoch) or crash-resume
# replay and the device-cache epochs diverge from the streamed path.
COLLATION_DETERMINISTIC_MODULES = (
    "graphs/collate.py",
    "graphs/batch.py",
    "graphs/csr.py",
    "graphs/sample.py",
    "graphs/packing.py",
    "preprocess/dataloader.py",
    "preprocess/splitting.py",
    # The streaming data plane: shard encoding and the epoch plan must be
    # wall-clock-free (byte-identical conversion, bit-exact streamed epochs
    # — docs/DATA_PLANE.md).
    "datasets/shards.py",
    "datasets/stream.py",
)

# Host-sync call patterns (attribute tails / dotted names / builtins).
HOST_SYNC_METHODS = frozenset({"item", "tolist", "block_until_ready"})
HOST_SYNC_DOTTED = frozenset(
    {
        "jax.device_get",
        "jax.block_until_ready",
        "np.asarray",
        "np.array",
        "numpy.asarray",
        "numpy.array",
    }
)
HOST_SYNC_BUILTINS = frozenset({"float", "int", "bool"})

# np.random attributes that are fine (explicitly-seeded generator plumbing).
SEEDED_NP_RANDOM = frozenset(
    {"default_rng", "Generator", "SeedSequence", "PCG64", "Philox"}
)


# ----------------------------------------------------- graftrace framework map
# The implicit main thread every entry point runs on.
MAIN_THREAD_ROOT = "main"

# Framework callables whose callable/iterable ARGUMENTS run on pipeline
# threads even though no ``threading.Thread(target=...)`` is visible at the
# call site (train/pipeline.py's two-stage feed): position/keyword -> the
# thread root the bound callable executes on. The same blindness
# TRACED_FACTORIES fixes for tracedness, fixed for runs-on-thread.
THREAD_CALLABLE_BINDINGS = {
    "DeviceFeed": {0: "feed-host", "iterable": "feed-host",
                   1: "feed-transfer", "transfer": "feed-transfer"},
    "_Prefetcher": {0: "feed-host", "iterable": "feed-host"},
    # The streaming loader's decode-ahead ring (datasets/stream.py): the
    # decode callable runs on the "hydragnn-shard-prefetch" daemon thread.
    # It must stay jax-free — decoded shards are host numpy; device work
    # happens downstream on the sanctioned transfer stage.
    "ShardRing": {1: "shard-prefetch", "decode": "shard-prefetch"},
}

# Factories whose NESTED function definitions run on a pipeline thread (the
# returned closure is installed as a DeviceFeed transfer stage; static
# analysis cannot see through the return, exactly like TRACED_FACTORIES).
THREAD_FACTORY_ROOTS = {
    "with_transfer_retries": "feed-transfer",
}

# Classes whose subclasses' methods run on per-connection server threads.
HTTP_HANDLER_BASES = frozenset({"BaseHTTPRequestHandler"})
HTTP_HANDLER_ROOT = "http-handler"

# Thread roots allowed to dispatch JAX work. Everything host-side must stay
# jax-free: the checkpoint writer thread serializes already-snapshotted host
# numpy, the batcher collates with numpy, HTTP handlers only block on
# futures. The transfer stage and the serve dispatcher ARE the sanctioned
# device paths (docs/INPUT_PIPELINE.md, docs/SERVING.md).
SANCTIONED_DISPATCH_ROOTS = frozenset(
    {MAIN_THREAD_ROOT, "feed-transfer", "hydragnn-serve-dispatch"}
)

# Dotted call prefixes that dispatch device work when executed.
JAX_DISPATCH_CALLS = frozenset(
    {
        "jax.device_put",
        "jax.device_get",
        "jax.block_until_ready",
        "jax.jit",
        "jax.pmap",
        "jax.eval_shape",
    }
)
JAX_DISPATCH_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.random.")

# Attribute types that synchronize themselves — writes THROUGH them need no
# guard (the binding write of the attribute cell itself still does, when it
# happens outside __init__).
THREAD_SAFE_TYPES = frozenset(
    {
        "queue.Queue",
        "queue.SimpleQueue",
        "queue.LifoQueue",
        "queue.PriorityQueue",
        "threading.Lock",
        "threading.RLock",
        "threading.Condition",
        "threading.Event",
        "threading.Semaphore",
        "threading.BoundedSemaphore",
        "threading.local",
        "collections.deque",
    }
)

# Container-mutator method names: ``self.X.append(...)`` mutates X.
MUTATOR_METHODS = frozenset(
    {
        "append", "extend", "insert", "add", "discard", "remove", "pop",
        "popitem", "clear", "update", "setdefault", "sort", "reverse",
    }
)

# Unbounded blocking calls by receiver type (graftrace types attributes from
# their __init__ construction): method names that park the calling thread.
BLOCKING_METHODS_BY_TYPE = {
    "queue.Queue": ("put", "get", "join"),
    "queue.LifoQueue": ("put", "get", "join"),
    "queue.PriorityQueue": ("put", "get", "join"),
    "queue.SimpleQueue": ("put", "get"),
    "threading.Event": ("wait",),
    "threading.Condition": ("wait", "wait_for"),
    "threading.Thread": ("join",),
}

# Process-fork entry points (fork-after-threads). subprocess.* is fork+exec
# and safe; multiprocessing with an explicit "spawn"/"forkserver" context is
# exempted at the call site.
FORK_CALLS = frozenset({"os.fork", "os.forkpty", "pty.fork"})
MP_PROCESS_CALLS = frozenset(
    {"multiprocessing.Process", "multiprocessing.Pool"}
)


# ----------------------------------------------------- graftproto framework map
# Collective call name tails: a call whose dotted tail is one of these (with
# or without the jax.lax/lax prefix) participates in the mesh's lockstep
# collective sequence. Ranks must trace IDENTICAL sequences or the XLA
# program deadlocks on a real multi-host mesh.
COLLECTIVE_CALLS = frozenset(
    {
        "psum",
        "pmean",
        "pmax",
        "pmin",
        "ppermute",
        "pshuffle",
        "all_gather",
        "all_to_all",
        "axis_index",
    }
)
# Names whose truthiness encodes rank identity: branching on one inside
# traced or lockstep code makes different ranks take different paths.
RANK_GUARD_NAMES = frozenset(
    {
        "rank",
        "shard_rank",
        "worker_rank",
        "process_index",
        "host_id",
        "is_leader",
        "leader",
    }
)

# Framework callables whose callable ARGUMENT runs as every member of a
# lockstep segment (run_workers spawns one thread per rank, all executing the
# bound fn with f-string thread names static analysis cannot read): the
# runs-on-thread analog of THREAD_CALLABLE_BINDINGS for the mesh harness.
# position/keyword -> the lockstep segment name the bound callable joins.
LOCKSTEP_CALLABLE_BINDINGS = {
    "run_workers": {1: "mesh-worker", "fn": "mesh-worker"},
}

# Rendezvous-barrier funnel methods: Class.method pairs that IMPLEMENT the
# barrier protocol (they are the barrier, not users of it) — their bodies are
# exempt from the barrier-protocol rules.
BARRIER_FUNNEL_METHODS = frozenset(
    {
        ("LoopbackRendezvous", "barrier"),
        ("ProxyRendezvous", "barrier"),
        ("LoopbackWorker", "barrier"),
        ("LoopbackRendezvous", "exchange"),
        ("LoopbackRendezvous", "broadcast"),
        ("ProxyRendezvous", "exchange"),
        ("ProxyRendezvous", "broadcast"),
        ("ProxyRendezvous", "allgather"),
    }
)

# Atomic persistence funnels: call tails that ARE the atomic-rename install
# (checkpoint/io.py's tmp+fsync+os.replace shapes). Control-plane state must
# flow through one of these; a bare open(path,"w")/shutil copy in a
# PERSISTENCE_STATE_MODULES function that never os.replace()s is a
# torn-state-hazard.
PERSISTENCE_CALLS = frozenset(
    {
        "atomic_write_json",
        "write_checkpoint_blob",
        "atomic_copy_file",
    }
)
# Module-path substrings whose functions hold crash-recovered control-plane
# state (the incarnation contract's scope). Telemetry/bench/dataset writers
# outside these paths are free to stream to open files.
PERSISTENCE_STATE_MODULES = (
    "checkpoint/io.py",
    "checkpoint/async_writer.py",
    "lifecycle/registry.py",
    "lifecycle/manager.py",
    "flywheel/loop.py",
    "parallel/elastic.py",
)
# Function names inside PERSISTENCE_STATE_MODULES that IMPLEMENT the atomic
# funnels (the open(tmp,"wb") + os.replace inside them is the mechanism, not
# a hazard).
PERSISTENCE_FUNNEL_FUNCTIONS = frozenset(
    {
        "atomic_write_json",
        "write_checkpoint_blob",
        "atomic_copy_file",
        "_unique_tmp",
    }
)

# Raw-deserialization entry points (pickle-load-outside-compat): the GSHD
# digest-verified containers replaced these in PR 16; surviving call sites
# are sanctioned v1-compat shims and carry reasoned suppressions.
PICKLE_LOAD_CALLS = frozenset(
    {
        "pickle.load",
        "pickle.loads",
        "torch.load",
    }
)
