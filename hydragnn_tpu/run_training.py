"""High-level E2E training driver — ``hydragnn_tpu.run_training(config_or_path)``
(reference /root/reference/hydragnn/run_training.py:40-122): env setup → process
bootstrap → data load/split → config completion → model build → optimizer +
ReduceLROnPlateau → log dir + config snapshot → optional warm start → epoch loop →
rank-0 checkpoint → timer report."""

from __future__ import annotations

import json
import os
from functools import singledispatch

import jax
import numpy as np

from .cache.jaxcache import place_jax_cache
from .models.create import create_model_config, init_model_variables
from .parallel.distributed import barrier, setup_ddp
from .preprocess.load_data import dataset_loading_and_splitting
from .train.train_validate_test import TrainingDriver, train_validate_test
from .train.trainer import create_train_state
from .utils.config_utils import get_log_name_config, update_config
from .utils.model import (
    checkpoint_exists,
    get_summary_writer,
    load_existing_model,
    load_existing_model_config,
    save_model,
)
from .utils.optimizer import ReduceLROnPlateau, select_optimizer
from .utils.print_utils import log, print_distributed, setup_log
from .utils.profile import Profiler
from .utils.time_utils import print_timers


@singledispatch
def run_training(config, mesh=None, supervise=False, max_restarts=3):
    raise TypeError("Input must be filename string or configuration dictionary.")


@run_training.register
def _(config_file: str, mesh=None, supervise=False, max_restarts=3):
    with open(config_file, "r") as f:
        config = json.load(f)
    return run_training(
        config, mesh=mesh, supervise=supervise, max_restarts=max_restarts
    )


@run_training.register
def _(config: dict, mesh=None, supervise=False, max_restarts=3):
    if supervise:
        # Structural-only gate here (deep=False needs no XLA backend, which
        # must not initialize before the children's jax.distributed
        # bootstrap); each child re-enters run_training and runs the full
        # gate (docs/STATIC_ANALYSIS.md).
        from .analysis.contracts import gate_config

        gate_config(config, deep=False)
        # Crash-resume supervisor (docs/FAULT_TOLERANCE.md): the training run
        # happens in child processes under a restart loop around the periodic
        # checkpoint + Training.resume contract. Returns the restart metadata
        # (also persisted at logs/<name>/supervisor.json), not the history —
        # the epoch history lives in the run's checkpoint meta.
        if mesh is not None:
            raise ValueError(
                "run_training(supervise=True) spawns child processes and "
                "cannot adopt an in-process mesh; configure the mesh via "
                "Training.graph_axis / multi-process launch instead"
            )
        from .faults.supervisor import run_supervised

        return run_supervised(config, max_restarts=max_restarts)
    os.environ.setdefault("SERIALIZED_DATA_PATH", os.getcwd())
    place_jax_cache()

    # Bootstrap BEFORE anything touches jax (setup_log rank-prefixes via
    # jax.process_index(), which initializes the XLA backend —
    # jax.distributed.initialize must run first).
    world_size, world_rank = setup_ddp()
    # Contract gate AFTER the distributed bootstrap (the eval_shape pass may
    # initialize the XLA backend) but BEFORE data loading and any compile
    # (docs/STATIC_ANALYSIS.md; HYDRAGNN_CHECK_CONFIG=full|structural|off).
    from .analysis.contracts import gate_config

    gate_config(config, mode="training")
    setup_log(get_log_name_config(config))
    # Config-level mesh request (beyond-reference): Training.graph_axis > 1
    # shards each graph's edges over that many devices (the FeSi_1024-style
    # large-graph axis) without any programmatic mesh plumbing — pure-JSON
    # configs reach the same path tests/test_largegraph.py exercises.
    from .parallel.distributed import config_graph_axis

    graph_axis = config_graph_axis(config)
    # graftelastic (docs/DISTRIBUTED.md "Elastic runbook"): a RESUMING
    # incarnation consumes the supervisor.json `mesh` block — a topology that
    # contradicts the persisted world/axis metadata fails loudly with both
    # topologies named, unless Training.elastic admits the new world size
    # (then it is a logged elastic transition: the loader re-shards and the
    # mesh rebuilds at the current world below, exactly as on a fresh start).
    if config["NeuralNetwork"]["Training"].get("resume"):
        from .faults.supervisor import read_supervisor_meta
        from .parallel.elastic import ElasticConfig, check_restart_topology

        sup_meta = read_supervisor_meta(get_log_name_config(config))
        if sup_meta.get("mesh"):
            transition = check_restart_topology(
                sup_meta["mesh"],
                world_size,
                graph_axis,
                ElasticConfig.from_training(
                    config["NeuralNetwork"]["Training"]
                ),
            )
            if transition is not None:
                log(
                    f"elastic restart: world_size "
                    f"{transition['from_world']} -> {transition['to_world']} "
                    f"({transition['kind']}) — loader re-shards and the mesh "
                    "rebuilds at the new size"
                )
                if world_rank == 0:
                    # Keep the persisted topology truthful for standalone
                    # resumes too — the supervisor's own restart loop records
                    # the same event when IT observes the change.
                    from .faults.supervisor import record_elastic_transition

                    record_elastic_transition(
                        get_log_name_config(config),
                        dict(transition, observed_by="run_training"),
                    )
    if mesh is None and (world_size > 1 or graph_axis > 1):
        # Reference semantics: training is data-parallel whenever the process
        # group is initialized (DDP wrap, reference run_training.py:78 +
        # distributed.py:216-226) — a multi-process launch without an explicit
        # mesh gets the global data mesh automatically.
        from .parallel.distributed import make_mesh

        mesh = make_mesh(graph_axis=graph_axis)
    # Say which devices the run holds: one process without a mesh trains on
    # device 0 alone, however many chips the host has.
    n_used = mesh.devices.size if mesh is not None else 1
    n_visible = jax.device_count()
    dev0 = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    log(
        f"devices: using {n_used} of {n_visible} "
        f"({dev0.platform}, {dev0.device_kind})"
        + (
            " — pass mesh=make_mesh() to run_training to train on all of them"
            if n_used < n_visible
            else ""
        )
    )

    verbosity = config["Verbosity"]["level"]
    train_loader, val_loader, test_loader, sampler_list = (
        dataset_loading_and_splitting(config=config)
    )
    config = update_config(config, train_loader, val_loader, test_loader)

    model = create_model_config(
        config=config["NeuralNetwork"]["Architecture"], verbosity=verbosity
    )
    example = next(iter(train_loader))
    variables = init_model_variables(model, example)
    # A mesh with a nontrivial 'graph' axis enables edge-sharded graph
    # parallelism (bound after init — collective axes are unbound outside the
    # sharded step).
    if mesh is not None and mesh.shape.get("graph", 1) > 1:
        model = model.clone(graph_axis="graph")

    optimizer = select_optimizer(
        config["NeuralNetwork"]["Training"]["optimizer"],
        config["NeuralNetwork"]["Training"]["learning_rate"],
        freeze_conv=config["NeuralNetwork"]["Architecture"]["freeze_conv_layers"],
    )
    scheduler = ReduceLROnPlateau(factor=0.5, patience=5, min_lr=0.00001)

    log_name = get_log_name_config(config)
    writer = get_summary_writer(log_name)
    barrier("logdir")
    os.makedirs("./logs/" + log_name, exist_ok=True)
    if world_rank == 0:
        # Startup cleanup: *.tmp litter from a crash mid-checkpoint-replace
        # in a previous incarnation (supervised restarts land here).
        from .utils.model import cleanup_stale_checkpoint_tmp

        cleanup_stale_checkpoint_tmp("./logs/" + log_name)
    with open("./logs/" + log_name + "/config.json", "w") as f:
        json.dump(config, f)

    # graftel (docs/OBSERVABILITY.md): point the flight recorder at this
    # run's log dir (guard trips / checkpoint fallbacks / engine poisonings
    # dump there) and turn on full span collection when asked — the
    # ``Telemetry`` config block or HYDRAGNN_TRACE=1.
    from . import telemetry

    tele_cfg = config.get("Telemetry") or {}
    collect_trace = bool(
        os.environ.get("HYDRAGNN_TRACE", "0") not in ("", "0", "false", "False")
        or tele_cfg.get("collect", 0)
    )
    telemetry.configure(
        run_dir="./logs/" + log_name,
        collect=collect_trace,
        jax_annotations=bool(tele_cfg.get("jax_annotations", 0)),
    )
    telemetry.install_jax_hooks()

    state = create_train_state(model, variables, optimizer)
    # Warm start (Training.continue / startfrom).
    new_vars, opt_state = load_existing_model_config(
        {"params": state.params, "batch_stats": state.batch_stats},
        config["NeuralNetwork"]["Training"],
        opt_state=state.opt_state,
    )
    state = state.replace(
        params=new_vars["params"],
        batch_stats=new_vars["batch_stats"],
        opt_state=opt_state,
    )

    # Crash resume (Training.resume — extension over the reference, which only
    # warm-starts weights and replays all epochs, SURVEY.md §5.3/5.4): pick up
    # THIS run's own checkpoint at the exact epoch/scheduler/history it saved.
    start_epoch = 0
    prior_history = None
    if config["NeuralNetwork"]["Training"].get("resume"):
        have = checkpoint_exists(log_name)
        if world_size > 1:
            # Every process replays the same epoch range — a rank resuming
            # while others start fresh would deadlock at the first mismatched
            # collective. Agree on the checkpoint's visibility up front.
            from jax.experimental import multihost_utils

            flags = multihost_utils.process_allgather(np.int32(have))
            if int(flags.min()) != int(flags.max()):
                raise RuntimeError(
                    "Training.resume: checkpoint for "
                    f"{log_name} is visible on some hosts but not others — "
                    "multi-host resume requires ./logs on shared storage"
                )
        if have:
            # Rank-0 save/restore points must not overlap across ranks: a
            # non-zero rank racing ahead here could read <name>.pk while a
            # rank-0 writer (a previous incarnation's final save, or a late
            # async flush) is still installing it.
            barrier("checkpoint_resume")
            # Verified load with the corruption fallback chain: a torn or
            # bit-flipped latest checkpoint falls back to the newest intact
            # keep_last_k entry instead of killing the (supervised) restart
            # loop (docs/CHECKPOINTING.md).
            new_vars, opt_state, meta = load_existing_model(
                {"params": state.params, "batch_stats": state.batch_stats},
                log_name,
                opt_state=state.opt_state,
                return_meta=True,
            )
            state = state.replace(
                params=new_vars["params"],
                batch_stats=new_vars["batch_stats"],
                opt_state=opt_state,
            )
            start_epoch = int(meta.get("epoch", 0))
            if meta.get("scheduler"):
                scheduler.load_state_dict(meta["scheduler"])
            prior_history = meta.get("history")
            print_distributed(
                verbosity, f"Resuming {log_name} from epoch {start_epoch}"
            )

    print_distributed(
        verbosity,
        "Starting training with the configuration: \n"
        + json.dumps(config, indent=4, sort_keys=True),
    )

    profiler = Profiler("./logs/" + log_name)
    profiler.setup(config.get("Profile"))

    # Fault tolerance (docs/FAULT_TOLERANCE.md): the non-finite step guard is
    # opt-in via the Training.fault_tolerance block (disabled = compiled
    # steps identical to the unguarded build); fault DRILLS come from the
    # HYDRAGNN_FAULTS env or the Training.faults spec string.
    training_cfg = config["NeuralNetwork"]["Training"]
    fault_plan = None
    if training_cfg.get("faults") and not os.environ.get("HYDRAGNN_FAULTS"):
        from .faults import FaultPlan

        fault_plan = FaultPlan(training_cfg["faults"])
    # graftcache (docs/COMPILE_CACHE.md): Training.compile_cache enables the
    # persistent compiled-executable store — a string is the store directory
    # (shareable across runs/replicas), any other truthy value defaults to
    # logs/<name>/compile_cache. The config fingerprint half of every key is
    # the digest of the completed Architecture + optimizer blocks, so a
    # resumed/restarted run hydrates its own executables and a changed model
    # or optimizer can never collide with them. The digest is computed
    # UNCONDITIONALLY: a store enabled via HYDRAGNN_COMPILE_CACHE alone must
    # carry the same key strength (optimizer hyperparameters like weight
    # decay change the compiled program without changing any tree shape).
    import hashlib

    compile_cache_fp = hashlib.sha256(
        json.dumps(
            {
                "architecture": config["NeuralNetwork"]["Architecture"],
                "optimizer": training_cfg.get("optimizer"),
                # Precision changes the compiled program (bf16 casts + the
                # loss-scale state machine) without changing any tree shape —
                # a key component (docs/PRECISION.md), belt to the driver's
                # flags suspenders. Folded in ONLY when a policy is active:
                # f32 runs must keep their pre-graftprec digests so existing
                # stores stay warm across the upgrade.
                **(
                    {
                        "precision": training_cfg["precision"],
                        "loss_scale": training_cfg.get("loss_scale"),
                    }
                    if training_cfg.get("precision") not in (None, "f32")
                    else {}
                ),
            },
            sort_keys=True,
            default=str,
        ).encode()
    ).hexdigest()
    if "compile_cache" in training_cfg:
        cc = training_cfg["compile_cache"]
        if not cc:
            # An EXPLICIT falsy value is a hard opt-out (the supervisor
            # documents `compile_cache: 0`) — it must also override an
            # exported HYDRAGNN_COMPILE_CACHE ("" disables, None defers).
            compile_cache_dir = ""
        else:
            compile_cache_dir = (
                cc
                if isinstance(cc, str)
                else "./logs/" + log_name + "/compile_cache"
            )
    else:
        compile_cache_dir = None  # defer to HYDRAGNN_COMPILE_CACHE
    driver = TrainingDriver(
        model,
        optimizer,
        state,
        mesh=mesh,
        verbosity=verbosity,
        fault_tolerance=training_cfg.get("fault_tolerance"),
        fault_plan=fault_plan,
        compile_cache=compile_cache_dir,
        compile_cache_fingerprint=compile_cache_fp,
        # graftprec (docs/PRECISION.md): Training.precision = "f32"|"bf16";
        # bf16 trains in bf16 compute against f32 master weights with dynamic
        # loss scaling (Training.loss_scale block tunes it). Since graftmesh
        # the policy also rides the mesh step (backoff lockstep post-psum).
        precision=training_cfg.get("precision"),
        loss_scale=training_cfg.get("loss_scale"),
        # graftmesh (docs/DISTRIBUTED.md): Training.grad_sync selects the
        # gradient-reduction arm of the mesh step ("single" | "bucketed" |
        # "ring"); grad_bucket_mb sizes the overlap buckets.
        grad_sync=training_cfg.get("grad_sync"),
        grad_bucket_mb=training_cfg.get("grad_bucket_mb"),
    )

    # Visualizer gets the test set's input node features and graph sizes
    # (reference train_validate_test.py:62-76).
    viz = None
    voi = config["NeuralNetwork"]["Variables_of_interest"]
    output_names = voi.get("output_names")
    if config["Visualization"].get("create_plots"):
        from .postprocess.visualizer import Visualizer

        node_feature = []
        nodes_num_list = []
        for sample in getattr(test_loader, "dataset", []):
            node_feature.extend(np.asarray(sample.x)[:, 0].tolist())
            nodes_num_list.append(int(np.asarray(sample.x).shape[0]))
        viz = Visualizer(
            "./logs/" + log_name,
            node_feature=node_feature,
            num_nodes_list=nodes_num_list,
            num_heads=len(model.output_dim),
            head_dims=list(model.output_dim),
            head_types=list(model.output_type),
        )

    history = train_validate_test(
        driver,
        train_loader,
        val_loader,
        test_loader,
        config["NeuralNetwork"]["Training"]["num_epoch"],
        writer=writer,
        scheduler=scheduler,
        profiler=profiler,
        verbosity=verbosity,
        visualizer=viz,
        output_names=output_names,
        plot_init_solution=config["Visualization"].get("plot_init_solution", True),
        plot_hist_solution=config["Visualization"].get("plot_hist_solution", False),
        checkpoint_name=log_name,
        checkpoint_every=config["NeuralNetwork"]["Training"].get(
            "periodic_checkpoint_every", 0
        ),
        checkpoint_keep_last_k=config["NeuralNetwork"]["Training"].get(
            "checkpoint_keep_last_k", 0
        ),
        checkpoint_async=bool(
            config["NeuralNetwork"]["Training"].get("checkpoint_async", 1)
        ),
        start_epoch=start_epoch,
        history=prior_history,
    )

    if world_rank == 0 and hasattr(train_loader, "write_size_histogram"):
        # Per-run size record for the ladder fitter (docs/SERVING.md
        # "Fitting a ladder from production histograms"): refit with
        # python -m hydragnn_tpu.graphs.packing fit-ladder --hist <file>.
        train_loader.write_size_histogram(
            "./logs/" + log_name + "/size_histogram.json"
        )

    if viz is not None:
        # Final test pass for the latest predictions; denormalize first when
        # requested (reference train_validate_test.py:141-163).
        _, _, true_values, predicted_values = driver.evaluate(
            test_loader, return_values=True
        )
        if voi.get("denormalize_output") and "y_minmax" in voi:
            from .postprocess.postprocess import output_denormalize

            true_values, predicted_values = output_denormalize(
                voi["y_minmax"], true_values, predicted_values
            )
        viz.create_plot_global(true_values, predicted_values, output_names)
        viz.create_scatter_plots(true_values, predicted_values, output_names)
        viz.plot_history(
            history,
            task_weights=list(model.task_weights),
            task_names=output_names,
        )

    save_model(
        {"params": driver.state.params, "batch_stats": driver.state.batch_stats},
        driver.state.opt_state,
        log_name,
        meta={
            "epoch": config["NeuralNetwork"]["Training"]["num_epoch"],
            "scheduler": scheduler.state_dict(),
            "history": history,
        },
        keep_last_k=config["NeuralNetwork"]["Training"].get(
            "checkpoint_keep_last_k", 0
        ),
    )
    # Non-zero ranks must not race ahead into a checkpoint load (e.g.
    # run_prediction immediately after training) while rank 0 is still writing.
    barrier("final_checkpoint")
    print_timers(verbosity)
    if world_rank == 0:
        # Telemetry artifacts (docs/OBSERVABILITY.md): the Prometheus
        # textfile of the registry (training gauges included) always; the
        # JSONL event log + Chrome/Perfetto trace when collection was on.
        run_dir = "./logs/" + log_name
        try:
            with open(os.path.join(run_dir, "train_metrics.prom"), "w") as f:
                f.write(telemetry.render_prometheus())
            if collect_trace:
                telemetry.export_events_jsonl(
                    os.path.join(run_dir, "trace_events.jsonl")
                )
                telemetry.export_chrome_trace(
                    os.path.join(run_dir, "trace_chrome.json")
                )
        except OSError as e:
            print_distributed(verbosity, f"telemetry export failed: {e}")
    return history
