"""Train driver for a token family (a sequence as a graph, a classifier head
over a vocabulary, routed experts): ``train_epochs``'s set-up and window, and
a check against the plain reference that routing cannot break by chance.

What is reused unchanged from ``train_epochs``: ``build`` (data, loaders,
config completion, model, the eager initializer), ``shaken`` and the warm-up.
The epoch loop below is ``train_epochs.run``'s, line for line where it can
be: ``train_validate_test`` one epoch at a time until the window is over,
``run_training``'s plateau scheduler, the same facts, the same ``correct``
rules (no compile in the window, finite losses, the validation loss under
the untrained model's).

What differs, and why ``reference.forward`` could not carry it:

* **Routing is discrete.** With seeded random weights the K-th and (K+1)-th
  of the router's scores are closer than the program's rounding at dozens of
  positions of any check; one flipped choice moves that token's logits by
  O(0.1-1) and, through attention, every later token's. So the program's
  forward also returns the experts it chose and the router's input in each
  routed layer (the model sows them; ``hydragnn_tpu/models/lfm2.py``), and
  the family file's ``logits`` FAILS unless each chosen set is a top-K of
  its own scores within a stated margin, then routes as the program did
  (``graftbench/families/lfm2.py`` has the two margins and their reasons).
* The check runs on ``check_sequences`` (2) test sequences at full width in
  the step's own padded shape: the reference costs ~1 TFLOP of host matmuls
  a 1024-token sequence at the published widths.
* Operations are counted over the rows the program's own counter says were
  routed to held experts, not over ``tokens x experts``; the window's sums
  of the three counters go into the facts for the ``moe_*`` readers.

What a token family needs beside this driver: a generator with the token and
next-token columns and positions ``(i, 0, 0)`` (``datagen/token_chain.py``), a
family file with ``logits``, ``compare``, ``counts`` and the margins
(``families/lfm2.py``), a configuration whose one node head has
``Variables_of_interest.loss`` "cross_entropy". Traffic parameters read here:
those of ``train_epochs`` (``layout`` "single" only) and ``check_sequences``.
"""

from __future__ import annotations

import time

import numpy as np

from graftbench import families, flops, memory
from graftbench.drivers.train_epochs import WARMUP_EPOCHS, build, shaken


def check_against_reference(model, variables, samples, pads, loader):
    """The program's forward on ``samples`` (whole sequences, collated into
    the step's padded shape ``pads``) against the family's plain reference,
    routing taken from the program and held to the family's margins.
    Returns (a dict of the readings, failure or None)."""
    import jax

    from hydragnn_tpu.graphs.collate import collate_graphs
    from hydragnn_tpu.models.lfm2 import INTERMEDIATES, split_intermediates
    from hydragnn_tpu.train.trainer import _apply_model

    family = families.load(model.conv_type)
    batch = collate_graphs(
        samples, loader.head_types, loader.head_dims, *pads,
        edge_dim=loader.edge_dim, with_positions=True,
    )
    outputs, sown = jax.jit(
        lambda p, b: _apply_model(
            model, p, {}, b, train=False, mutable=[INTERMEDIATES]
        )
    )(variables["params"], batch)
    routing, _ = split_intermediates(sown[INTERMEDIATES])
    got = np.asarray(outputs[0])
    routing = jax.tree_util.tree_map(np.asarray, routing)
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        host = jax.devices()[0]
    read = dict(max_diff=0.0, rel_l2=0.0, route_margin=0.0, router_margin=0.0)
    fail, start = None, 0
    with jax.default_device(host):
        params = jax.tree_util.tree_map(
            lambda a: jax.numpy.asarray(np.asarray(a)), dict(variables["params"])
        )
        for g, s in enumerate(samples):
            rows = slice(start, start + s.num_nodes)
            start += s.num_nodes
            want, report = family.logits(
                model, params,
                {"x": np.asarray(s.x, np.float32), "pos": np.asarray(s.pos, np.float32)},
                {k: {kk: vv[rows] for kk, vv in v.items()} for k, v in routing.items()},
            )
            worst, rel, why = family.compare(got[rows], want)
            read["max_diff"] = max(read["max_diff"], worst)
            read["rel_l2"] = max(read["rel_l2"], rel)
            for name, limit in (("route_margin", family.ROUTE_EPS),
                                ("router_margin", family.ROUTER_EPS)):
                read[name] = max(read[name], report[name])
                if report[name] > limit:
                    why = why or (
                        f"{name} {report[name]:.3e} beyond {limit}: the program "
                        "chose experts that are no top-K of the reference's scores"
                    )
            if why and fail is None:
                fail = f"sequence {g}: {why}"
    return read, fail


def run(cell) -> dict:
    import jax

    from hydragnn_tpu import telemetry
    from hydragnn_tpu.models.lfm2 import COUNTERS
    from hydragnn_tpu.train.train_validate_test import (
        TrainingDriver,
        train_validate_test,
    )
    from hydragnn_tpu.train.trainer import create_train_state
    from hydragnn_tpu.utils.optimizer import (
        ReduceLROnPlateau,
        get_learning_rate,
        select_optimizer,
    )

    if cell.traffic.get("layout", "single") != "single":
        raise SystemExit("[graftbench] train_tokens runs the single-chip layout only")
    b = build(cell)
    config, model, variables = b["config"], b["model"], b["variables"]
    train_loader, val_loader, test_loader = b["loaders"]
    training = config["NeuralNetwork"]["Training"]
    family = families.load(model.conv_type)
    why_not = []

    count = int(cell.traffic.get("check_sequences", 2))
    t_check = time.perf_counter()
    read, fail = check_against_reference(
        model, shaken(variables, cell.seed), test_loader.dataset[:count],
        train_loader.pad_sizes, train_loader,
    )
    print(
        f"[graftbench] program vs plain float32 reference on {count} sequences "
        f"at full width: logits max |diff| {read['max_diff']:.3e} (atol "
        f"{family.ATOL}, rtol {family.RTOL}), relative L2 {read['rel_l2']:.3e} "
        f"(limit {family.REL_L2}); routing margin {read['route_margin']:.3e} "
        f"(eps {family.ROUTE_EPS}), router margin {read['router_margin']:.3e} "
        f"(eps {family.ROUTER_EPS}); {time.perf_counter() - t_check:.1f}s",
        flush=True,
    )
    if fail:
        why_not.append("reference: " + fail)

    optimizer = select_optimizer(
        training["optimizer"], training["learning_rate"],
        freeze_conv=b["arch"]["freeze_conv_layers"],
    )
    # run_training's scheduler, as train_epochs has it.
    scheduler = ReduceLROnPlateau(
        factor=0.5, patience=int(cell.traffic.get("plateau_patience", 5)),
        min_lr=0.00001,
    )
    t_init = time.perf_counter()
    state = jax.block_until_ready(create_train_state(model, variables, optimizer))
    del variables, b["variables"]
    init_s = b["init_s"] + time.perf_counter() - t_init
    print(
        f"[graftbench] eager initializer (init_model_variables + "
        f"create_train_state): {init_s:.1f}s of the set-up", flush=True,
    )
    driver = TrainingDriver(
        model, optimizer, state, verbosity=0,
        precision=training.get("precision"),
        grad_sync=training.get("grad_sync"),
    )
    del state
    programs = memory.ProgramMemory(cell)
    for attr in ("train_step", "eval_step", "epoch_scan"):
        if hasattr(driver, attr):
            setattr(driver, attr, programs.watch(attr, getattr(driver, attr)))
    cell.mark("model + reference check")

    history = None
    epoch = 0

    def one_epoch():
        nonlocal history, epoch
        history = train_validate_test(
            driver, train_loader, val_loader, test_loader, epoch + 1,
            scheduler=scheduler, verbosity=0, start_epoch=epoch,
            history=history, checkpoint_every=0,
        )
        epoch += 1

    loss_untrained = float(driver.evaluate(val_loader)[0])
    for _ in range(WARMUP_EPOCHS):
        one_epoch()
    print(
        f"[graftbench] first epoch's train loss (seed {cell.seed}): "
        f"{history['total_loss_train'][0]:.8f}", flush=True,
    )
    warm = epoch

    facts = dict(
        epochs=0, train_graphs=0, epoch_wall_s=0.0, train_epoch_wall_s=0.0,
        feed_wait_s=0.0, h2d_s=0.0, step_s=0.0, init_s=init_s,
        epoch_s=[], train_epoch_s=[], **dict.fromkeys(COUNTERS, 0.0),
    )
    train_loader.reset_padding_stats()
    programs.recording = False
    t0 = cell.begin_window()
    while time.perf_counter() - t0 < cell.seconds:
        t_e = time.perf_counter()
        with telemetry.span("graftbench.epoch", epoch=epoch):
            one_epoch()
        facts["epoch_s"].append(time.perf_counter() - t_e)
        facts["epoch_wall_s"] += facts["epoch_s"][-1]
        gauges = telemetry.gauges_snapshot()
        facts["train_epoch_s"].append(gauges["train/epoch_wall_s"])
        facts["train_epoch_wall_s"] += gauges["train/epoch_wall_s"]
        facts["feed_wait_s"] += gauges["train/feed_wait_s_per_epoch"]
        facts["h2d_s"] += gauges["train/h2d_s_per_epoch"]
        facts["step_s"] += gauges["train/step_s_per_epoch"]
        for name in COUNTERS:  # sums over the epoch's steps and routed layers
            facts[name] += gauges.get(f"train/{name}_per_epoch", 0.0)
        facts["epochs"] += 1
        facts["train_graphs"] += len(train_loader.dataset)
    window_s = cell.end_window(t0)

    pad = train_loader.padding_stats()
    steps = pad["batches"]
    # Pool and head as flops.py counts them for every family; the encoder
    # over the rows the program says it routed to held experts.
    nodes, edges = pad["real_nodes"], pad["real_edges"]

    def encoder_ops(rows):
        return 3 * flops.total(family.counts(b["arch"], nodes, edges, rows)[0])["ops"]

    counted = (
        flops.train_step(b["arch"], nodes, edges, pad["real_graphs"])["ops"]
        - encoder_ops(None) + encoder_ops(facts["moe_rows_held"])
    )
    facts.update(
        window_s=window_s,
        eval_wall_s=facts["epoch_wall_s"] - facts["train_epoch_wall_s"],
        batches=pad["batches"], steps=steps,
        real_nodes=pad["real_nodes"], pad_nodes=pad["pad_nodes"],
        real_edges=pad["real_edges"], pad_edges=pad["pad_edges"],
        real_graphs=pad["real_graphs"],
        step_ops=counted / max(steps, 1), chips=len(cell.devices),
        reference=read,
    )
    losses = [float(v) for v in history["total_loss_train"]]
    print(
        f"[graftbench] {facts['epochs']} epochs, {steps} steps, "
        f"{facts['train_graphs']} train graphs in {window_s:.3f}s; train "
        f"loss per epoch {[round(v, 6) for v in losses]}; seconds per epoch "
        f"{[round(v, 3) for v in facts['epoch_s']]}, of them training "
        f"{[round(v, 3) for v in facts['train_epoch_s']]}; rows routed to "
        f"held experts a step {facts['moe_rows_held'] / max(steps, 1):.1f}",
        flush=True,
    )
    loss_val = float(history["total_loss_val"][-1])
    lr = get_learning_rate(driver.state.opt_state)
    print(
        f"[graftbench] validation loss {loss_untrained:.6f} untrained -> "
        f"{loss_val:.6f} after {epoch} epochs; learning rate "
        f"{training['learning_rate']:.6g} -> "
        f"{lr if lr is None else format(lr, '.6g')} (plateau patience "
        f"{scheduler.patience})", flush=True,
    )
    if not np.isfinite(losses + [loss_val, loss_untrained]).all():
        why_not.append(f"non-finite loss {losses} {loss_val} {loss_untrained}")
    elif not loss_val < loss_untrained:
        why_not.append(
            f"validation loss {loss_val} after {epoch} epochs is not under "
            f"the untrained model's {loss_untrained}"
        )
    if any(history["xla_compiles"][warm:]):
        why_not.append(f"XLA compiles per epoch {history['xla_compiles']}")
    temps = programs.temp_bytes()
    return dict(
        attempted=facts["epochs"], failed=0, why_not=why_not, facts=facts,
        extra={"losses": losses, "hydragnn_config": b["as_given"],
               "learning_rate": lr, "program_temp_bytes": temps},
        memory=memory.peak(cell.devices, temps),
        end_to_end={"train_graphs_per_s": facts["train_graphs"] / window_s},
    )
