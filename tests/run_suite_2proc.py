"""Run the test suite under TWO rendezvousing processes — the analog of the
reference CI's distributed pass ``mpirun -n 2 python -m pytest --with-mpi``
(/root/reference/.github/workflows/CI.yml:47-52).

Each rank runs pytest over tests/ with OMPI-style env; ``setup_ddp`` inside the
high-level API rendezvouses the two processes via jax.distributed, and
run_training/run_prediction auto-shard over the global 2-device mesh, so the
full convergence matrix (tests/test_graphs_<family>.py, one file a conv family
— every conv family, unchanged single-process accuracy thresholds) trains
data-parallel. Serial-only tests are skipped by tests/conftest.py, exactly
like the reference's @pytest.mark.mpi_skip.

    python tests/run_suite_2proc.py [extra pytest args...]

A custom selection (anything other than the default ``tests/``) additionally
gets the PNA single-head convergence cell appended
(tests/test_graphs_pna.py::pytest_train_model[ci.json-PNA], reference-CI
thresholds), so a narrowed 2-process run is never plumbing-only — it always
trains at least one real model data-parallel to convergence, mirroring the
reference CI's ``mpirun -n 2`` coverage. Opt out with --no-convergence-cell.

graftmesh (docs/DISTRIBUTED.md): on backends without cross-process
collectives (XLA:CPU), the spawn arm is environmentally dead — the suite
then RUNS the loopback-harness DP cells (2 logical workers, real 2-device
virtual mesh) instead of skipping, and the exit code gates on THAT arm's
verdict; the artifact records ``loopback`` + ``spawn_skipped``.

Exit code 0 iff the distributed arm that ran passed (both ranks on capable
backends; the loopback cells otherwise).
"""

from __future__ import annotations

import os
import re
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main() -> int:
    import argparse
    import time

    # Per-round provenance artifact ({passed, skipped, seconds, rc} per rank)
    # so suite regressions are mechanically visible, not only in stray logs.
    # allow_abbrev=False: unknown args forward to pytest verbatim — a prefix
    # like --art must not be swallowed as an abbreviation of --artifact.
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--artifact", default=None)
    ap.add_argument("--no-convergence-cell", action="store_true")
    args, argv = ap.parse_known_args()
    artifact = args.artifact

    port = _free_port()
    extra = argv or ["tests/"]
    # The real-convergence guarantee (docstring above): a narrowed selection
    # still trains PNA single-head to the reference thresholds under the
    # 2-process mesh. The full default selection already contains it.
    convergence_cell = "tests/test_graphs_pna.py::pytest_train_model[ci.json-PNA]"
    if (
        argv
        and not args.no_convergence_cell
        and not any(a.startswith("tests/test_graphs_") for a in argv)
        # A -k expression would also filter the appended node id; the caller
        # controls selection semantics then, so leave it untouched.
        and "-k" not in argv
    ):
        extra = list(extra) + [convergence_cell]
    t_start = time.time()
    procs = []
    logs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            OMPI_COMM_WORLD_SIZE="2",
            OMPI_COMM_WORLD_RANK=str(rank),
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            # One virtual CPU device per process: a true 2-device global mesh,
            # mirroring the reference's 2-rank Gloo CI.
            HYDRAGNN_HOST_DEVICES="1",
        )
        path = os.path.join(REPO, f"suite_2proc_rank{rank}.log")
        log = open(path, "w")
        logs.append((path, log))
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider"]
                + extra,
                cwd=REPO,
                env=env,
                stdout=log,
                stderr=subprocess.STDOUT,
            )
        )
    rcs = [p.wait() for p in procs]
    elapsed = round(time.time() - t_start, 1)
    ran = []
    per_rank = []
    # Backend capability gate (mirrors tests/test_multiprocess.py): when a
    # rank's failures are XLA's own "Multiprocess computations aren't
    # implemented" (CPU backend has no cross-process collectives), the
    # 2-process suite is environmentally impossible — report a PRECISE skip
    # (exit 0, reason in the artifact) instead of a red that names nothing
    # fixable in the repo. ROADMAP item 5 (portable collective layer) is
    # the real fix.
    no_mp_marker = "Multiprocess computations aren't implemented"
    backend_lacks_mp = False
    for rank, (path, log) in enumerate(logs):
        log.close()
        with open(path) as f:
            text = f.read()
        if rcs[rank] != 0 and no_mp_marker in text:
            backend_lacks_mp = True
        m = re.search(r"(\d+) passed", text)
        skipped = re.search(r"(\d+) skipped", text)
        ran.append(int(m.group(1)) if m else 0)
        per_rank.append(
            {
                "rank": rank,
                "passed": ran[-1],
                "skipped": int(skipped.group(1)) if skipped else 0,
                "rc": rcs[rank],
            }
        )
    with open(logs[0][0]) as f:
        sys.stdout.write(f.read())
    print(f"rank return codes: {rcs}; tests passed per rank: {ran}")
    skip_reason = None
    loopback = None
    if backend_lacks_mp:
        # graftmesh upgrade: the spawn arm is environmentally impossible on
        # this backend, but that no longer means "skipped" — the REAL
        # distributed run falls back to the loopback harness (2 logical
        # workers, per-rank loader shards, shard_map DP over a 2-device
        # virtual mesh; docs/DISTRIBUTED.md "Harness modes"): the loopback
        # DP e2e cells from tests/test_multiprocess.py run to completion
        # and the artifact records mode="loopback".
        skip_reason = (
            "spawn arm skipped: backend lacks multiprocess collectives "
            f"(XLA: {no_mp_marker!r}); ran the loopback harness arm instead"
        )
        print(f"SPAWN ARM DEAD: {skip_reason}")
        t_lb = time.time()
        lb_env = dict(os.environ)
        # The rank launches above pinned HYDRAGNN_HOST_DEVICES=1 semantics;
        # the loopback arm needs a >1-device virtual topology regardless of
        # what this process inherited — pin it explicitly.
        lb_env["HYDRAGNN_HOST_DEVICES"] = "2"
        lb_env.pop("OMPI_COMM_WORLD_SIZE", None)
        lb_env.pop("OMPI_COMM_WORLD_RANK", None)
        lb_proc = subprocess.run(
            [
                sys.executable, "-m", "pytest", "-q",
                "-p", "no:cacheprovider",
                "tests/test_multiprocess.py::pytest_two_worker_loopback_dp_training",
                "tests/test_multiprocess.py::pytest_two_worker_loopback_overlap_arm_agrees",
            ],
            cwd=REPO,
            env=lb_env,
            capture_output=True,
            text=True,
        )
        sys.stdout.write(lb_proc.stdout[-4000:])
        m_lb = re.search(r"(\d+) passed", lb_proc.stdout)
        loopback = {
            "mode": "loopback",
            "workers": 2,
            "passed": int(m_lb.group(1)) if m_lb else 0,
            "rc": lb_proc.returncode,
            "seconds": round(time.time() - t_lb, 1),
        }
        print(f"LOOPBACK ARM: {loopback}")
    ok = (
        (loopback["rc"] == 0 and loopback["passed"] > 0)
        if loopback is not None
        else all(rc == 0 for rc in rcs) and all(n > 0 for n in ran)
    )
    if artifact:
        import json

        with open(artifact, "w") as f:
            json.dump(
                {
                    "ts_utc": time.strftime(
                        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_start)
                    ),
                    "seconds": elapsed,
                    "selection": extra,
                    "ranks": per_rank,
                    "ok": ok,
                }
                | ({"spawn_skipped": skip_reason} if skip_reason else {})
                | ({"loopback": loopback} if loopback else {}),
                f,
                indent=2,
            )
    if loopback is not None:
        # The loopback arm IS the distributed run on this backend: its
        # verdict gates the exit code (no more unconditional-0 skip).
        return 0 if ok else 1
    if not all(n > 0 for n in ran):
        # All-skipped still exits 0 from pytest; a selection outside the
        # multi-process-safe set must not read as a green distributed run.
        print("ERROR: a rank executed zero tests — selection is serial-only?")
        return 1
    return 0 if all(rc == 0 for rc in rcs) else 1


if __name__ == "__main__":
    sys.exit(main())
