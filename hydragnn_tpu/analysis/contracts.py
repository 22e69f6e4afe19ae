"""Static config/shape contract checker — ``check_config``.

Catches broken training/serving configs BEFORE any device compile: the
structural half cross-checks the JSON against the framework's config contract
(head spec vs dataset descriptors, dtype validity, bucket feasibility,
donation/distribution conflicts), and the shape half runs ``jax.eval_shape``
over the FULL stack — model init, forward, multi-head loss, and the guarded
train step — against a padded-arena example batch built from the declared
descriptors. ``eval_shape`` only traces with abstract values: nothing is
compiled, no device memory moves, and every input (batch AND rng) is passed
as a ``ShapeDtypeStruct`` so the check cannot even allocate a device array —
safe to run before ``jax.distributed.initialize`` ordering matters.

Every failure is one actionable line tagged with a stable code:

  missing-field     a key the entry point will dereference is absent
  bad-head-spec     head types/indices/weights/heads blocks disagree
  bad-arch          the Architecture block cannot build a model
  dtype-mismatch    compute_dtype is not a floating dtype
  bad-precision     Training.precision / loss_scale / serve --precision
                    nonsense (unknown arm, int8 for training, non-positive
                    scale knobs, quantized serve without a tolerance bound)
  oob-bucket        a bucket/batch/ladder size cannot hold the data
  bad-mesh          distributed/mesh config nonsense (axis sizes vs the
                    visible device count, graph_axis with the CSR/sorted
                    contract explicitly disabled, unknown grad_sync arm,
                    non-positive grad bucket size, elastic worker-range
                    knobs that cannot be satisfied) — docs/DISTRIBUTED.md
  bad-elastic-timing  elastic liveness timing that silently turns a slow
                    epoch into a hang-kill: heartbeat_s at or under the
                    pump's tick resolution (interval_s = heartbeat_s/4), or
                    heartbeat_s at or above the ProxyRendezvous wire
                    deadlines (post 10 s, barrier 300 s) — the coordinator
                    would drop a healthy worker's connection before its
                    next beat could land — docs/DISTRIBUTED.md "Elastic
                    runbook"
  bad-router        multi-replica router config nonsense (replica count /
                    hash-ring weights / admission classes without deadlines /
                    fleet ladder-memory blowout) — docs/SERVING.md
                    "Multi-replica tier"
  bad-lifecycle     live-model-lifecycle nonsense (shadow fraction outside
                    (0, 1], shadow/canary without a tolerance bound, swap
                    target whose architecture fingerprint mismatches the
                    serving config, rollback with keep_last_k < 2) —
                    docs/SERVING.md "Live model lifecycle"
  bad-flywheel      continuous-learning flywheel nonsense (auto-promotion
                    without a positive shadow tolerance, drift thresholds
                    outside (0, 1) or inverted, refit interval shorter than
                    the shadow gate window, keep_last_k < 3 with
                    auto-promotion enabled, flywheel with checkpoint_async
                    off) — docs/FLYWHEEL.md
  bad-pilot         fleet-autopilot nonsense (inverted/degenerate scale or
                    brownout watermarks, cooldown shorter than the replica
                    spin-up wall, an empty or severity-unordered brownout
                    ladder, a per-tenant quota wider than the global
                    in-flight bound, min_replicas > max_replicas) —
                    docs/SERVING.md "Fleet autopilot"
  donation-misuse   config requests a donating step that would alias buffers
  shape-mismatch    eval_shape found inconsistent shapes/dtypes end to end

Exposed as ``python -m hydragnn_tpu.analysis check-config <json>`` and called
at the top of run_training / run_prediction / serve startup.

The eval_shape pass always uses AdamW regardless of ``Training.optimizer``:
the contract being checked is model/loss/grad-step shape agreement, which is
optimizer-independent, and tracing an LBFGS linesearch would multiply the
check's cost for no additional shape coverage.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

HEAD_KINDS = ("graph", "node")


class ConfigContractError(ValueError):
    """One or more config contract violations; ``errors`` carries
    (code, message) pairs, the str() is the first message + a count."""

    def __init__(self, errors: List[Tuple[str, str]]):
        self.errors = errors
        first = f"[{errors[0][0]}] {errors[0][1]}" if errors else "config invalid"
        extra = f" (+{len(errors) - 1} more)" if len(errors) > 1 else ""
        super().__init__(first + extra)


def _get(config: Dict[str, Any], *path, default=None):
    cur: Any = config
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return default
        cur = cur[key]
    return cur


# (fingerprint, mode) -> (errors, skipped, eval_shape_s). The eval_shape half
# is pure in the model-relevant config subset, so repeated entry-point calls
# on the same config (epoch-loop tests, supervisor restarts) pay the tracing
# cost once per process.
_SHAPE_CACHE: Dict[Tuple[str, str], Tuple[list, list, Any]] = {}


def check_config(
    config,
    mode: str = "training",
    bucket_ladder: "Optional[Sequence[Tuple[int, int]] | str]" = None,
    strict: bool = True,
    deep: bool = True,
    serve_precision: Optional[str] = None,
    serve_tolerance: Optional[float] = None,
    router: Optional[Dict[str, Any]] = None,
    lifecycle: Optional[Dict[str, Any]] = None,
    flywheel: Optional[Dict[str, Any]] = None,
    pilot: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Validate a training or serving config statically. Returns the report
    dict; with ``strict`` (the default) raises :class:`ConfigContractError`
    on any violation instead. ``deep=False`` skips the ``jax.eval_shape``
    pass (structural checks only — the entry points use this when
    ``HYDRAGNN_CHECK_CONFIG=structural``). ``bucket_ladder`` accepts parsed
    ``(N_pad, E_pad)`` rungs or any CLI spec string — ``"NxE,..."`` or
    ``"auto:<path>"`` (resolved via graphs/packing.resolve_ladder_spec).
    ``serve_precision``/``serve_tolerance`` are the serve CLI's arm flags
    (docs/PRECISION.md): quantized arms without a positive tolerance bound
    are a ``bad-precision`` finding here, before the checkpoint loads.
    ``router`` is the front-router config dict (the route CLI passes
    ``{"replicas", "classes", "load_factor", "vnodes", ...}``); router
    nonsense is a ``bad-router`` finding through this same gate.
    ``lifecycle`` is the graftswap config dict
    (``{"shadow_fraction", "tolerance", "swap_target",
    "expected_fingerprint", "rollback", "keep_last_k"}``); lifecycle
    nonsense is a ``bad-lifecycle`` finding through this same gate.
    ``flywheel`` is the graftloop config dict (``FlywheelConfig.to_json()``
    or the supervisor's flywheel block: ``{"auto_promote",
    "shadow_tolerance", "drift_high", "drift_low", "refit_interval_s",
    "gate_window_s", "keep_last_k"}``); flywheel nonsense is a
    ``bad-flywheel`` finding through this same gate.
    ``pilot`` is the graftpilot config dict (``AutopilotConfig.to_json()``:
    ``{"scale_high", "scale_low", "cooldown_s", "spinup_wall_s",
    "min_replicas", "max_replicas", "ladder", "tenant_inflight_quota",
    "global_inflight_limit", ...}``); autopilot nonsense is a ``bad-pilot``
    finding through this same gate."""
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    if mode not in ("training", "prediction", "serving"):
        raise ValueError(f"unknown check-config mode {mode!r}")
    errors: List[Tuple[str, str]] = []
    skipped: List[str] = []

    arch = _get(config, "NeuralNetwork", "Architecture") or {}
    voi = _get(config, "NeuralNetwork", "Variables_of_interest") or {}
    training = _get(config, "NeuralNetwork", "Training") or {}
    completed = all(k in arch for k in ("input_dim", "output_dim", "output_type"))

    _check_structure(config, arch, voi, training, mode, completed, errors)
    _check_head_spec(config, arch, voi, completed, errors)
    _check_dtype(arch, errors)
    _check_precision(
        arch, training, mode, serve_precision, serve_tolerance, errors
    )
    _check_buckets(config, arch, training, bucket_ladder, mode, errors)
    _check_mesh(training, deep, errors)
    if router is not None:
        _check_router(router, bucket_ladder, errors)
    if lifecycle is not None:
        _check_lifecycle(lifecycle, arch, training, completed, errors)
    if flywheel is not None:
        _check_flywheel(flywheel, training, errors)
    if pilot is not None:
        _check_pilot(pilot, errors)
    _check_donation(training, errors)
    _check_aggregation_path(arch, errors)
    _check_position_family(arch, errors)

    eval_shape_s = None
    if not errors and not deep:
        skipped.append("eval_shape: disabled (deep=False)")
    elif not errors:
        key = (
            json.dumps(
                {
                    "arch": arch,
                    "voi": voi,
                    "ds": _get(config, "Dataset"),
                    # Precision changes the TRACED training step (bf16 casts
                    # + the loss-scale state machine), so it must key the
                    # shape cache too.
                    "precision": training.get("precision"),
                    "loss_scale": training.get("loss_scale"),
                },
                sort_keys=True,
                default=str,
            ),
            mode,
        )
        cached = _SHAPE_CACHE.get(key)
        if cached is not None:
            cached_errors, cached_skipped, eval_shape_s = cached
            errors.extend(cached_errors)
            skipped.extend(cached_skipped)
        else:
            shape_errors: List[Tuple[str, str]] = []
            shape_skipped: List[str] = []
            eval_shape_s = _check_shapes(
                config, arch, voi, training, mode, completed,
                shape_errors, shape_skipped,
            )
            _SHAPE_CACHE[key] = (shape_errors, shape_skipped, eval_shape_s)
            errors.extend(shape_errors)
            skipped.extend(shape_skipped)

    report = {
        "ok": not errors,
        "mode": mode,
        "completed_config": completed,
        "errors": [{"code": c, "message": m} for c, m in errors],
        "skipped": skipped,
        "eval_shape_s": eval_shape_s,
    }
    if errors and strict:
        raise ConfigContractError(errors)
    return report


def gate_config(
    config,
    mode: str = "training",
    bucket_ladder=None,
    deep=True,
    serve_precision=None,
    serve_tolerance=None,
    router=None,
    lifecycle=None,
    flywheel=None,
    pilot=None,
):
    """The ONE entry-point gate shared by run_training / run_prediction /
    serve startup: honors ``HYDRAGNN_CHECK_CONFIG`` (``full`` default,
    ``structural`` skips the eval_shape pass, ``off`` disables the gate) and
    raises :class:`ConfigContractError` with one actionable line on a broken
    config — before data loading and before any device compile."""
    import os

    level = os.environ.get("HYDRAGNN_CHECK_CONFIG", "full")
    if level == "off":
        return None
    return check_config(
        config,
        mode=mode,
        bucket_ladder=bucket_ladder,
        deep=deep and level != "structural",
        serve_precision=serve_precision,
        serve_tolerance=serve_tolerance,
        router=router,
        lifecycle=lifecycle,
        flywheel=flywheel,
        pilot=pilot,
    )


# ------------------------------------------------------------------ structure
def _check_structure(config, arch, voi, training, mode, completed, errors):
    if not isinstance(_get(config, "NeuralNetwork"), dict):
        errors.append(("missing-field", "config has no NeuralNetwork block"))
        return
    for key in ("model_type", "hidden_dim", "num_conv_layers", "output_heads",
                "task_weights"):
        if key not in arch:
            errors.append(
                ("missing-field", f"NeuralNetwork.Architecture.{key} is missing")
            )
    if mode == "serving":
        if not completed:
            missing = [
                k
                for k in ("input_dim", "output_dim", "output_type")
                if k not in arch
            ]
            errors.append(
                (
                    "missing-field",
                    "serving needs a COMPLETED config (missing Architecture."
                    + "/".join(missing)
                    + ") — pass the logs/<name>/config.json snapshot "
                    "run_training wrote, not the raw input config",
                )
            )
        return
    # training mode: the data-driven completion contract needs these.
    if _get(config, "Verbosity", "level") is None:
        errors.append(("missing-field", "Verbosity.level is missing"))
    ds = _get(config, "Dataset")
    if not isinstance(ds, dict):
        errors.append(
            ("missing-field", "Dataset block is missing (training mode "
             "loads and splits from Dataset.path)")
        )
    else:
        for key in ("name", "path"):
            if key not in ds:
                errors.append(("missing-field", f"Dataset.{key} is missing"))
        if isinstance(ds.get("path"), dict) and not ds["path"]:
            errors.append(("missing-field", "Dataset.path is empty"))
        kinds_used = set(voi.get("type") or ())
        for kind in ("graph", "node"):
            feat = f"{kind}_features"
            if kind in kinds_used and not completed:
                if not isinstance(_get(ds, feat, "dim"), list):
                    errors.append(
                        (
                            "missing-field",
                            f"Dataset.{feat}.dim is missing but the config "
                            f"declares a {kind!r} head — completion cannot "
                            "derive its output width",
                        )
                    )
    for key in ("input_node_features", "type", "output_index"):
        # Completed configs may omit type/output_index (Architecture carries
        # output_type/output_dim) but never input_node_features.
        if key not in voi and not (completed and key != "input_node_features"):
            errors.append(
                (
                    "missing-field",
                    f"NeuralNetwork.Variables_of_interest.{key} is missing",
                )
            )
    # batch_size feeds the loaders on every entry point; the epoch-loop
    # knobs only matter when a training loop will actually run.
    required_training = (
        ("batch_size",)
        if mode == "prediction"
        else ("batch_size", "learning_rate", "num_epoch")
    )
    for key in required_training:
        if key not in training:
            errors.append(
                ("missing-field", f"NeuralNetwork.Training.{key} is missing")
            )


# ------------------------------------------------------------------ head spec
def _check_head_spec(config, arch, voi, completed, errors):
    types = list(
        arch.get("output_type") if completed else (voi.get("type") or ())
    )
    if not types:
        return
    bad_kinds = [t for t in types if t not in HEAD_KINDS]
    if bad_kinds:
        errors.append(
            (
                "bad-head-spec",
                f"unknown head kind(s) {bad_kinds} — every entry of "
                "Variables_of_interest.type must be 'graph' or 'node'",
            )
        )
    indices = voi.get("output_index")
    if indices is not None and len(indices) != len(types):
        errors.append(
            (
                "bad-head-spec",
                f"{len(types)} head type(s) but {len(indices)} "
                "output_index entries — the lists must be parallel",
            )
        )
    weights = arch.get("task_weights")
    if isinstance(weights, list) and len(weights) != len(types):
        errors.append(
            (
                "bad-head-spec",
                f"task_weights has {len(weights)} entries for {len(types)} "
                "head(s) — one loss weight per head",
            )
        )
    heads = arch.get("output_heads")
    if isinstance(heads, dict):
        for kind in sorted(set(types) & set(HEAD_KINDS)):
            if kind not in heads:
                errors.append(
                    (
                        "bad-head-spec",
                        f"config declares a {kind!r} head but "
                        f"Architecture.output_heads has no {kind!r} block",
                    )
                )
    # Mirrors completion's _stage_edge_dim assertion, but as one line up
    # front: only the edge-consuming conv stacks accept edge_features.
    if arch.get("edge_features") and arch.get("model_type") not in (
        "PNA",
        "CGCNN",
    ):
        errors.append(
            (
                "bad-arch",
                f"Architecture.edge_features declared but model_type "
                f"{arch.get('model_type')!r} does not consume per-edge "
                "features (PNA/CGCNN only)",
            )
        )
    if completed:
        dims = arch.get("output_dim") or []
        if len(dims) != len(types):
            errors.append(
                (
                    "bad-head-spec",
                    f"completed config disagrees with itself: {len(dims)} "
                    f"output_dim entries for {len(types)} output_type entries",
                )
            )
    elif indices is not None and isinstance(_get(config, "Dataset"), dict):
        for kind in HEAD_KINDS:
            dims = _get(config, "Dataset", f"{kind}_features", "dim")
            if not isinstance(dims, list):
                continue
            for i, (t, idx) in enumerate(zip(types, indices)):
                if t == kind and not (
                    isinstance(idx, int) and 0 <= idx < len(dims)
                ):
                    errors.append(
                        (
                            "bad-head-spec",
                            f"head {i}: output_index {idx} is outside "
                            f"Dataset.{kind}_features.dim (len {len(dims)})",
                        )
                    )


# ---------------------------------------------------------------------- dtype
def _check_dtype(arch, errors):
    cd = arch.get("compute_dtype")
    if cd is None:
        return
    import numpy as np

    try:
        dt = np.dtype(
            {"bfloat16": np.float32}.get(cd, cd)
        )  # np has no bfloat16; jnp accepts it — validate the rest via numpy
        is_float = np.issubdtype(dt, np.floating) or cd == "bfloat16"
    except TypeError:
        errors.append(
            (
                "dtype-mismatch",
                f"Architecture.compute_dtype {cd!r} is not a dtype",
            )
        )
        return
    if not is_float:
        errors.append(
            (
                "dtype-mismatch",
                f"Architecture.compute_dtype {cd!r} is not a floating dtype "
                "— mixed-precision compute must be float (e.g. 'bfloat16')",
            )
        )


# ------------------------------------------------------------------ precision
def _check_precision(
    arch, training, mode, serve_precision, serve_tolerance, errors
):
    """graftprec config contract (docs/PRECISION.md): unknown precision
    strings, int8 for TRAINING, loss-scale knob nonsense, and quantized
    serving without a tolerance bound are one actionable line here — before
    the checkpoint loads or the first step compiles."""
    from ..precision.policy import (
        QUANTIZED_SERVE_PRECISIONS,
        SERVE_PRECISIONS,
        TRAIN_PRECISIONS,
        LossScaleConfig,
    )

    if mode == "serving":
        if serve_precision is None:
            return
        if serve_precision not in SERVE_PRECISIONS:
            errors.append(
                (
                    "bad-precision",
                    f"serving precision {serve_precision!r} is not one of "
                    f"{SERVE_PRECISIONS}",
                )
            )
        elif serve_precision in QUANTIZED_SERVE_PRECISIONS:
            if not isinstance(serve_tolerance, (int, float)) or isinstance(
                serve_tolerance, bool
            ) or serve_tolerance <= 0:
                errors.append(
                    (
                        "bad-precision",
                        f"quantized serving (--precision {serve_precision}) "
                        "requires a positive --tolerance bound — the "
                        "bit-exactness contract is relaxed, never silently "
                        f"dropped; got {serve_tolerance!r}",
                    )
                )
        elif serve_tolerance is not None:
            errors.append(
                (
                    "bad-precision",
                    "--tolerance is a quantized-arm knob; --precision f32 "
                    "serves under the bit-exactness contract and accepts "
                    "none",
                )
            )
        return
    prec = training.get("precision")
    if prec is not None:
        if prec == "int8":
            errors.append(
                (
                    "bad-precision",
                    "Training.precision='int8' is not a training mode — "
                    "int8 is a quantized SERVING arm (--precision int8); "
                    "train with 'bf16' and quantize at serve time",
                )
            )
        elif prec not in TRAIN_PRECISIONS:
            errors.append(
                (
                    "bad-precision",
                    f"Training.precision {prec!r} is not one of "
                    f"{TRAIN_PRECISIONS}",
                )
            )
        elif prec == "f32" and arch.get("compute_dtype") == "bfloat16":
            errors.append(
                (
                    "bad-precision",
                    "Training.precision='f32' contradicts "
                    "Architecture.compute_dtype='bfloat16' — drop one (the "
                    "policy would silently not be full f32)",
                )
            )
        elif prec == "bf16" and arch.get("compute_dtype") not in (
            None,
            "bfloat16",
        ):
            # The other direction of the same contradiction: the driver only
            # clones onto bf16 compute when compute_dtype is unset, so an
            # explicit non-bf16 dtype would silently train at THAT dtype
            # with pointless loss scaling armed.
            errors.append(
                (
                    "bad-precision",
                    "Training.precision='bf16' contradicts "
                    f"Architecture.compute_dtype="
                    f"{arch.get('compute_dtype')!r} — bf16 training needs "
                    "compute_dtype unset (the policy sets it) or 'bfloat16'",
                )
            )
        if (
            prec == "bf16"
            and str(training.get("optimizer", "")).upper() == "LBFGS"
        ):
            errors.append(
                (
                    "bad-precision",
                    "Training.precision='bf16' (dynamic loss scaling) does "
                    "not support LBFGS — the zoom linesearch is not "
                    "scale-invariant under dynamic rescaling; use a "
                    "first-order optimizer",
                )
            )
    ls = training.get("loss_scale")
    if ls is None:
        return
    if not isinstance(ls, dict):
        errors.append(
            (
                "bad-precision",
                f"Training.loss_scale must be a dict of scale knobs "
                f"(init/backoff/growth/growth_interval), got "
                f"{type(ls).__name__}",
            )
        )
        return
    try:
        LossScaleConfig.from_config(ls)
    except (TypeError, ValueError) as e:
        errors.append(
            ("bad-precision", f"Training.loss_scale is invalid: {e}")
        )


# -------------------------------------------------------------------- buckets
def _check_router(router, bucket_ladder, errors):
    """Front-router config contract (docs/SERVING.md "Multi-replica tier"):
    replica-count / hash-ring-weight / admission-class nonsense and a
    fleet-wide ladder-memory blowout (every replica compiles or hydrates
    the WHOLE bucket ladder — N replicas x R rungs executables resident)
    are one actionable ``bad-router`` line before any engine is built."""
    import math

    replicas = router.get("replicas", 1)
    n_replicas = None
    if isinstance(replicas, int) and not isinstance(replicas, bool):
        n_replicas = replicas
        if replicas < 1:
            errors.append(
                (
                    "bad-router",
                    f"router needs at least 1 replica, got {replicas}",
                )
            )
    elif isinstance(replicas, (list, tuple)):
        n_replicas = len(replicas)
        if not replicas:
            errors.append(("bad-router", "router replica list is empty"))
        for i, spec in enumerate(replicas):
            weight = (
                spec.get("weight", 1.0) if isinstance(spec, dict) else spec
            )
            try:
                w = float(weight)
            except (TypeError, ValueError):
                w = float("nan")
            if not math.isfinite(w) or w <= 0:
                errors.append(
                    (
                        "bad-router",
                        f"replica #{i} hash-ring weight must be a positive "
                        f"finite number, got {weight!r}",
                    )
                )
    else:
        errors.append(
            (
                "bad-router",
                f"router 'replicas' must be a count or a list, got "
                f"{type(replicas).__name__}",
            )
        )

    classes = router.get("classes")
    if classes is not None:
        if not isinstance(classes, dict) or not classes:
            errors.append(
                (
                    "bad-router",
                    "router 'classes' must be a non-empty mapping of "
                    "admission-class name -> {deadline_s}",
                )
            )
        else:
            for name, spec in classes.items():
                deadline = (
                    spec.get("deadline_s")
                    if isinstance(spec, dict)
                    else spec
                )
                try:
                    d = float(deadline)
                except (TypeError, ValueError):
                    d = float("nan")
                if not math.isfinite(d) or d <= 0:
                    errors.append(
                        (
                            "bad-router",
                            f"admission class {name!r} has no positive "
                            f"finite deadline_s (got {deadline!r}) — an SLO "
                            "class without a deadline cannot shed load",
                        )
                    )

    load_factor = router.get("load_factor", 1.25)
    try:
        lf = float(load_factor)
    except (TypeError, ValueError):
        lf = float("nan")
    if not math.isfinite(lf) or lf < 1.0:
        errors.append(
            (
                "bad-router",
                f"load_factor must be a finite number >= 1 (bounded-load "
                f"consistent hashing), got {load_factor!r}",
            )
        )

    vnodes = router.get("vnodes", 64)
    if not isinstance(vnodes, int) or isinstance(vnodes, bool) or vnodes < 1:
        errors.append(
            ("bad-router", f"vnodes must be an integer >= 1, got {vnodes!r}")
        )

    # Fleet ladder memory: resolve the rung count when a ladder is known.
    rungs = None
    if isinstance(bucket_ladder, str):
        try:
            from ..graphs.packing import resolve_ladder_spec

            rungs = len(resolve_ladder_spec(bucket_ladder))
        except Exception:  # noqa: BLE001 — _check_buckets reports the spec
            rungs = None
    elif bucket_ladder is not None:
        try:
            rungs = len(list(bucket_ladder))
        except TypeError:
            rungs = None
    max_fleet_buckets = router.get("max_fleet_buckets", 128)
    if (
        not isinstance(max_fleet_buckets, int)
        or isinstance(max_fleet_buckets, bool)
        or max_fleet_buckets < 1
    ):
        errors.append(
            (
                "bad-router",
                "max_fleet_buckets must be an integer >= 1, got "
                f"{max_fleet_buckets!r}",
            )
        )
        max_fleet_buckets = 128
    if rungs and n_replicas and n_replicas * rungs > max_fleet_buckets:
        errors.append(
            (
                "bad-router",
                f"{n_replicas} replicas x {rungs} ladder rungs = "
                f"{n_replicas * rungs} resident executables exceeds the "
                f"fleet budget {max_fleet_buckets} — shrink the ladder, "
                "the fleet, or raise router.max_fleet_buckets",
            )
        )


def _expected_param_fingerprint(arch) -> Optional[str]:
    """Param-tree fingerprint of the (completed) serving config's model,
    via ``jax.eval_shape`` over ``model.init`` — ShapeDtypeStructs only, so
    nothing compiles and no device memory moves (the same zero-allocation
    discipline as the eval_shape gate). The fingerprint hashes key paths /
    shapes / dtypes, which SDS leaves carry."""
    import jax
    import numpy as np

    from ..checkpoint.format import param_fingerprint
    from ..models.create import create_model_config, make_example_batch

    arch2 = dict(arch)
    arch2.setdefault("freeze_conv_layers", False)
    model = create_model_config(config=arch2, verbosity=0)
    example = make_example_batch(
        arch["input_dim"],
        arch["output_dim"],
        arch["output_type"],
        edge_dim=arch.get("edge_dim"),
        num_nodes=int(arch.get("num_nodes") or 8),
        with_positions=model.needs_positions,
    )
    batch_sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        example,
    )
    key_sds = jax.ShapeDtypeStruct((2,), np.uint32)
    variables = jax.eval_shape(
        lambda b, k: model.init({"params": k, "dropout": k}, b, train=False),
        batch_sds,
        key_sds,
    )
    return param_fingerprint(variables["params"])


def _check_lifecycle(lifecycle, arch, training, completed, errors):
    """graftswap config contract (docs/SERVING.md "Live model lifecycle"):
    shadow-fraction / tolerance / rollback-retention / swap-target nonsense
    is one actionable ``bad-lifecycle`` line before any engine mutates."""
    import math

    frac = lifecycle.get("shadow_fraction")
    if frac is not None:
        try:
            f = float(frac)
        except (TypeError, ValueError):
            f = float("nan")
        if not math.isfinite(f) or not (0.0 < f <= 1.0):
            errors.append(
                (
                    "bad-lifecycle",
                    f"shadow fraction must be in (0, 1], got {frac!r} — 0 "
                    "mirrors nothing (the gate can never go green) and >1 "
                    "is not a sampling fraction",
                )
            )
        tol = lifecycle.get("tolerance")
        if (
            not isinstance(tol, (int, float))
            or isinstance(tol, bool)
            or not math.isfinite(float(tol))
            or tol <= 0
        ):
            errors.append(
                (
                    "bad-lifecycle",
                    "shadow/canary serving requires a positive tolerance "
                    "bound (the diff gate's definition of 'matches live'); "
                    f"got {tol!r}",
                )
            )
    if lifecycle.get("rollback"):
        k = lifecycle.get(
            "keep_last_k", training.get("checkpoint_keep_last_k")
        )
        if not isinstance(k, int) or isinstance(k, bool) or k < 2:
            errors.append(
                (
                    "bad-lifecycle",
                    f"rollback requires checkpoint_keep_last_k >= 2 (got "
                    f"{k!r}) — the previous version must still exist in the "
                    "retention manifest to be restorable",
                )
            )
    target = lifecycle.get("swap_target")
    if target:
        fp = None
        try:
            from ..checkpoint.format import file_content_identity

            _identity, header = file_content_identity(str(target))
            fp = header.get("param_fingerprint")
        except Exception as e:  # noqa: BLE001 — every read failure is a finding
            errors.append(
                (
                    "bad-lifecycle",
                    f"swap target {target!r} is not a verifiable v2 "
                    f"checkpoint: {e}",
                )
            )
        if fp:
            expected = lifecycle.get("expected_fingerprint")
            if expected is None and completed:
                try:
                    expected = _expected_param_fingerprint(arch)
                except Exception:  # noqa: BLE001 — bad-arch reported elsewhere
                    expected = None
            if expected and fp != expected:
                errors.append(
                    (
                        "bad-lifecycle",
                        f"swap target {target!r} was saved from a different "
                        "architecture than the serving config (param-tree "
                        "fingerprint mismatch) — a hot swap is weights-only; "
                        "an architecture change needs a replica rebuild",
                    )
                )


def _check_flywheel(flywheel, training, errors):
    """graftloop config contract (docs/FLYWHEEL.md): a misconfigured
    flywheel does not fail loudly — it silently promotes garbage (no
    tolerance), flaps the ladder (inverted thresholds), starves its own
    shadow gate (refit < gate window), or GC-races its rollback chain
    (keep_last_k < 3). Each is one actionable ``bad-flywheel`` line before
    the control thread starts."""
    import math

    def _num(key):
        v = flywheel.get(key)
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return None
        f = float(v)
        return f if math.isfinite(f) else None

    auto = bool(flywheel.get("auto_promote", True))
    tol = _num("shadow_tolerance")
    if auto and (tol is None or tol <= 0):
        errors.append(
            (
                "bad-flywheel",
                "auto-promotion requires a positive shadow_tolerance — "
                "without a diff bound the shadow gate has no definition of "
                "'candidate matches live' and promotion is unguarded; got "
                f"{flywheel.get('shadow_tolerance')!r}",
            )
        )
    high = _num("drift_high")
    low = _num("drift_low")
    for key, val in (("drift_high", high), ("drift_low", low)):
        if flywheel.get(key) is not None and (
            val is None or not (0.0 < val < 1.0)
        ):
            errors.append(
                (
                    "bad-flywheel",
                    f"{key} must be in (0, 1) — histogram distance is "
                    "total-variation, so 0 fires on any noise and >= 1 can "
                    f"never fire; got {flywheel.get(key)!r}",
                )
            )
    if high is not None and low is not None and not (low < high):
        errors.append(
            (
                "bad-flywheel",
                f"drift thresholds must satisfy low < high (got low={low!r} "
                f"high={high!r}) — equal or inverted thresholds remove the "
                "hysteresis band and the refit actuator can flap on "
                "boundary noise",
            )
        )
    refit = _num("refit_interval_s")
    gate_w = _num("gate_window_s")
    if refit is not None and gate_w is not None and refit < gate_w:
        errors.append(
            (
                "bad-flywheel",
                f"refit_interval_s ({refit!r}) must be >= gate_window_s "
                f"({gate_w!r}) — re-evaluating drift faster than the shadow "
                "gate can accumulate samples lets a ladder swap land "
                "mid-judgement and invalidate the gate's comparisons",
            )
        )
    if auto:
        k = flywheel.get(
            "keep_last_k", training.get("checkpoint_keep_last_k")
        )
        if isinstance(k, int) and not isinstance(k, bool) and k < 3:
            errors.append(
                (
                    "bad-flywheel",
                    f"auto-promotion requires checkpoint_keep_last_k >= 3 "
                    f"(got {k!r}) — live, previous, and the in-flight "
                    "candidate each need a retained slot or retention GC "
                    "races the promotion it is feeding",
                )
            )
    ckpt_async = flywheel.get(
        "checkpoint_async", training.get("checkpoint_async")
    )
    if ckpt_async is not None and not ckpt_async:
        errors.append(
            (
                "bad-flywheel",
                "the flywheel requires checkpoint_async — its staging hook "
                "rides the async saver's post-save callback, and a "
                "synchronous save would stall the training step for the "
                "full stage-and-arm round trip",
            )
        )


def _check_pilot(pilot, errors):
    """graftpilot config contract (docs/SERVING.md "Fleet autopilot"): a
    misconfigured autopilot does not fail loudly — it flaps the fleet
    (inverted watermarks), double-scales every wave (cooldown shorter than
    the spin-up wall), browns out the HIGHEST-priority class first (an
    unordered ladder), or lets one tenant fill the whole router (quota
    wider than the global bound). Each is one actionable ``bad-pilot``
    line before the control thread starts. Mirrors
    ``pilot.AutopilotConfig.__post_init__`` — what the gate rejects, the
    constructor rejects too."""
    import math

    def _num(key):
        v = pilot.get(key)
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return None
        f = float(v)
        return f if math.isfinite(f) else None

    for low_key, high_key in (
        ("scale_low", "scale_high"),
        ("brownout_low", "brownout_high"),
    ):
        low, high = _num(low_key), _num(high_key)
        present = pilot.get(low_key) is not None or pilot.get(high_key) is not None
        if present and (
            low is None or high is None or not (0.0 <= low < high)
        ):
            errors.append(
                (
                    "bad-pilot",
                    f"{low_key}/{high_key} must satisfy 0 <= low < high "
                    f"(got {pilot.get(low_key)!r}/{pilot.get(high_key)!r}) — "
                    "an inverted or degenerate pair removes the dead band "
                    "and the autoscaler flaps on boundary noise",
                )
            )
    cooldown = _num("cooldown_s")
    spinup = _num("spinup_wall_s")
    if cooldown is not None and spinup is not None and cooldown < spinup:
        errors.append(
            (
                "bad-pilot",
                f"cooldown_s ({cooldown!r}) must cover spinup_wall_s "
                f"({spinup!r}) — re-deciding while the previous replica is "
                "still warming double-scales on every wave",
            )
        )
    ladder = pilot.get("ladder")
    if ladder is not None:
        from ..pilot.brownout import parse_ladder

        try:
            parse_ladder(ladder)
        except (ValueError, TypeError) as e:
            errors.append(("bad-pilot", f"brownout ladder invalid: {e}"))
    quota = _num("tenant_inflight_quota")
    bound = _num("global_inflight_limit")
    if quota is not None and bound is not None and quota > bound:
        errors.append(
            (
                "bad-pilot",
                f"tenant_inflight_quota ({quota!r}) exceeds "
                f"global_inflight_limit ({bound!r}) — one tenant's bulkhead "
                "would be wide enough to fill the whole fleet, which is no "
                "bulkhead at all",
            )
        )
    mn = _num("min_replicas")
    mx = _num("max_replicas")
    if mn is not None and mn < 0:
        errors.append(
            ("bad-pilot", f"min_replicas must be >= 0, got {mn!r}")
        )
    if mx is not None and mx < 1:
        errors.append(
            ("bad-pilot", f"max_replicas must be >= 1, got {mx!r}")
        )
    if mn is not None and mx is not None and mn > mx:
        errors.append(
            (
                "bad-pilot",
                f"min_replicas ({mn!r}) > max_replicas ({mx!r}) — the "
                "reconciler's clamp range is empty and the target is "
                "undefined",
            )
        )
    idle = _num("idle_ticks_to_zero")
    if idle is not None and idle > 0 and mn is not None and mn != 0:
        errors.append(
            (
                "bad-pilot",
                f"idle_ticks_to_zero ({idle!r}) requires min_replicas == 0 "
                f"(got {mn!r}) — scale-to-zero retires the whole fleet",
            )
        )


def _check_buckets(config, arch, training, bucket_ladder, mode, errors):
    bs = training.get("batch_size")
    if bs is not None and (not isinstance(bs, int) or bs < 1):
        errors.append(
            ("oob-bucket", f"Training.batch_size {bs!r} must be an int >= 1")
        )
    nb = _get(config, "Dataset", "num_buckets")
    if nb is not None and (not isinstance(nb, int) or nb < 1):
        errors.append(
            ("oob-bucket", f"Dataset.num_buckets {nb!r} must be an int >= 1")
        )
    ls = _get(config, "Dataset", "ladder_step")
    if ls is not None and ls not in ("pow2", "mult64"):
        errors.append(
            (
                "oob-bucket",
                f"Dataset.ladder_step {ls!r} must be 'pow2' or 'mult64' "
                "(the pad round-up ladder, graphs/packing.round_up_step)",
            )
        )
    pk = _get(config, "Dataset", "packing")
    if pk is not None and not isinstance(pk, bool):
        errors.append(
            ("oob-bucket", f"Dataset.packing {pk!r} must be a bool")
        )
    if isinstance(bucket_ladder, str):
        # Spec forms ("NxE,..." literal, "auto:<histogram-or-ladder.json>")
        # resolve through ONE parser so CLI and checker can never disagree;
        # any resolution failure (bad literal, missing/garbled auto file,
        # empty histogram) is an actionable oob-bucket line here instead of
        # a stack trace after the checkpoint loaded.
        from ..graphs.packing import resolve_ladder_spec

        try:
            bucket_ladder = resolve_ladder_spec(bucket_ladder)
        except Exception as e:  # noqa: BLE001 — every parse error is a finding
            errors.append(
                (
                    "oob-bucket",
                    f"bucket ladder spec {bucket_ladder!r} is invalid: {e}",
                )
            )
            bucket_ladder = None
    if bucket_ladder is not None:
        num_nodes = arch.get("num_nodes")
        best_n = 0
        for rung in bucket_ladder:
            # Explicit pair check first: a stray string would otherwise index
            # as its characters ("64" -> (6, 4)) and mis-validate.
            if not isinstance(rung, (tuple, list)) or len(rung) != 2:
                errors.append(
                    ("oob-bucket", f"bucket ladder rung {rung!r} is not (N_pad, E_pad)")
                )
                continue
            try:
                n, e = int(rung[0]), int(rung[1])
            except (TypeError, ValueError):
                errors.append(
                    ("oob-bucket", f"bucket ladder rung {rung!r} is not (N_pad, E_pad)")
                )
                continue
            if n < 2 or e < 1:
                errors.append(
                    (
                        "oob-bucket",
                        f"bucket ladder rung ({n}, {e}) cannot hold a graph "
                        "(N_pad needs >= 1 real + 1 padding node)",
                    )
                )
            best_n = max(best_n, n)
        if num_nodes and best_n and best_n <= int(num_nodes):
            errors.append(
                (
                    "oob-bucket",
                    f"largest bucket ladder rung N_pad={best_n} cannot fit a "
                    f"single num_nodes={num_nodes} graph (collate needs "
                    "N_pad > total nodes)",
                )
            )
    ga = training.get("graph_axis")
    if ga is not None and (not isinstance(ga, int) or ga < 1):
        errors.append(
            ("oob-bucket", f"Training.graph_axis {ga!r} must be an int >= 1")
        )


# ----------------------------------------------------------------- mesh/graftmesh
def _check_mesh(training, deep, errors):
    """graftmesh config contract (docs/DISTRIBUTED.md): mesh-axis requests
    the visible devices cannot satisfy, a graph-partitioned run with the
    CSR/sorted aggregation contract explicitly disabled, unknown
    gradient-sync arms, nonsense bucket sizes, and unsatisfiable elastic
    worker ranges are one actionable ``bad-mesh`` line each — before any
    mesh builds or a shard_map step compiles.

    bf16 + mesh is deliberately NOT a finding since graftmesh: the
    loss-scale state machine rides the mesh step with the backoff update in
    lockstep post-psum (train/trainer._dp_local_graftmesh), closing ROADMAP
    item 3's explicit rejection.

    The device-count comparison runs only under ``deep`` — counting devices
    initializes the XLA backend, which the structural-only gate (the
    supervisor's pre-spawn path) must never do."""
    import os

    ga = training.get("graph_axis")
    ga = ga if isinstance(ga, int) and ga >= 1 else 1
    if ga > 1 and os.environ.get("HYDRAGNN_SEGMENT_SORTED") in (
        "0", "false", "False",
    ):
        errors.append(
            (
                "bad-mesh",
                f"Training.graph_axis={ga} with HYDRAGNN_SEGMENT_SORTED "
                "disabled: graph-partitioned training's halo/edge-cut "
                "exchange is built on the CSR/sorted contract "
                "(ops localize row_ptr per edge shard) — re-enable the "
                "sorted path or drop graph_axis",
            )
        )
    if ga > 1 and deep:
        import jax

        n = jax.device_count()
        if ga > n:
            errors.append(
                (
                    "bad-mesh",
                    f"Training.graph_axis={ga} exceeds the {n} visible "
                    "device(s) — the mesh cannot build; shrink graph_axis "
                    "or pin more virtual devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)",
                )
            )
    gs = training.get("grad_sync")
    if gs is not None:
        from ..parallel.overlap import GRAD_SYNC_MODES

        if gs not in GRAD_SYNC_MODES:
            errors.append(
                (
                    "bad-mesh",
                    f"Training.grad_sync {gs!r} is not one of "
                    f"{GRAD_SYNC_MODES}",
                )
            )
    gbm = training.get("grad_bucket_mb")
    if gbm is not None and (
        isinstance(gbm, bool)
        or not isinstance(gbm, (int, float))
        or gbm <= 0
    ):
        errors.append(
            (
                "bad-mesh",
                f"Training.grad_bucket_mb {gbm!r} must be a positive number "
                "(megabytes per gradient all-reduce bucket)",
            )
        )
    elastic = training.get("elastic")
    if elastic is None:
        return
    if not isinstance(elastic, dict):
        errors.append(
            (
                "bad-mesh",
                "Training.elastic must be a dict of worker-range knobs "
                f"(min_workers/max_workers/heartbeat_s), got "
                f"{type(elastic).__name__}",
            )
        )
        return
    unknown = sorted(
        set(elastic) - {"min_workers", "max_workers", "heartbeat_s"}
    )
    if unknown:
        errors.append(
            ("bad-mesh", f"Training.elastic has unknown knob(s) {unknown}")
        )
    mn, mx = elastic.get("min_workers", 1), elastic.get("max_workers")
    bounds_ok = True
    for name, val in (("min_workers", mn), ("max_workers", mx)):
        if val is not None and (
            isinstance(val, bool) or not isinstance(val, int) or val < 1
        ):
            errors.append(
                (
                    "bad-mesh",
                    f"Training.elastic.{name} {val!r} must be an int >= 1",
                )
            )
            bounds_ok = False
    if bounds_ok and mx is not None and mn is not None and mn > mx:
        errors.append(
            (
                "bad-mesh",
                f"Training.elastic min_workers={mn} > max_workers={mx} — "
                "no world size satisfies the range",
            )
        )
    hb = elastic.get("heartbeat_s")
    if hb is not None and (
        isinstance(hb, bool) or not isinstance(hb, (int, float)) or hb <= 0
    ):
        errors.append(
            (
                "bad-mesh",
                f"Training.elastic.heartbeat_s {hb!r} must be a positive "
                "number of seconds",
            )
        )
    elif hb is not None:
        # Liveness timing (bad-elastic-timing): the HeartbeatPump posts
        # every heartbeat_s/4 and the supervisor declares a worker dead
        # after ~heartbeat_s without a beat, while the ProxyRendezvous wire
        # path enforces its own read/write deadlines. A heartbeat window
        # that does not fit strictly inside those deadlines (or a pump tick
        # below timer resolution) silently turns every slow epoch into a
        # hang-kill — flag it here, before any worker spawns.
        from ..parallel.loopback import _BARRIER_TIMEOUT_S, _POST_TIMEOUT_S

        pump_s = hb / 4.0
        if pump_s < 0.05:
            errors.append(
                (
                    "bad-elastic-timing",
                    f"Training.elastic.heartbeat_s={hb} puts the heartbeat "
                    f"pump interval at {pump_s:.3g}s (heartbeat_s/4) — "
                    "below timer resolution, the pump cannot hold the "
                    "margin; raise heartbeat_s to at least 0.2",
                )
            )
        if hb >= _POST_TIMEOUT_S:
            errors.append(
                (
                    "bad-elastic-timing",
                    f"Training.elastic.heartbeat_s={hb} is not strictly "
                    f"under the ProxyRendezvous post deadline "
                    f"({_POST_TIMEOUT_S:g}s) — a beat delayed by one slow "
                    "post RPC overshoots the liveness window and the "
                    "supervisor kills a healthy worker",
                )
            )
        if hb >= _BARRIER_TIMEOUT_S:
            errors.append(
                (
                    "bad-elastic-timing",
                    f"Training.elastic.heartbeat_s={hb} is not strictly "
                    f"under the ProxyRendezvous barrier deadline "
                    f"({_BARRIER_TIMEOUT_S:g}s) — the rendezvous would time "
                    "out a world that is merely waiting for the next "
                    "heartbeat-paced quiesce",
                )
            )


# ---------------------------------------------------------- aggregation path
def _check_aggregation_path(arch, errors):
    """Reject configs whose resolved conv family cannot ride the sorted/CSR
    edge layout (models/families.py:SORTED_PATH_FAMILIES). On TPU the sorted
    path is the DEFAULT (ops/segment_sorted.sorted_enabled) — a family
    outside the registry would silently fall back to the unsorted XLA
    scatter path, the exact regression class BENCH_r05 measured at 0.47x.
    Every shipped family is registered since PR 7 (GAT joined via the
    self-term rework); this check exists so a future family cannot land
    half-ported without an explicit opt-out."""
    import os

    mt = arch.get("model_type")
    if mt is None:
        return  # missing-field already reported
    from ..models.families import CONV_TYPES, SORTED_PATH_FAMILIES

    if mt not in CONV_TYPES:
        return  # bad-arch surfaces at model build; don't double-report
    if mt in SORTED_PATH_FAMILIES:
        return
    if os.environ.get("HYDRAGNN_SEGMENT_SORTED") in ("0", "false", "False"):
        return  # the sorted path is explicitly pinned off — scatter is intended
    errors.append(
        (
            "bad-arch",
            f"model_type {mt!r} is not registered in SORTED_PATH_FAMILIES "
            "(models/families.py): on TPU its aggregation would silently fall "
            "back to the unsorted scatter path — register the family's "
            "sorted/CSR aggregation or pin HYDRAGNN_SEGMENT_SORTED=0",
        )
    )


# ----------------------------------------------------------- position families
def _check_position_family(arch, errors):
    """The families that compute their edge geometry in the step from
    ``GraphBatch.positions`` (models/families.py:POSITION_FAMILIES — PaiNN):
    the cutoff and the basis size must be there, and the edge vector must be
    the difference of the two positions, which it is not across a periodic
    cell boundary."""
    from ..models.families import POSITION_FAMILIES, TOKEN_STACKS

    mt = arch.get("model_type")
    if mt not in POSITION_FAMILIES:
        return
    if mt in TOKEN_STACKS:
        # Positions are the nodes' places in their sequences: no cutoff, no
        # basis, no cell. What the stack needs instead (its sizes class,
        # the registry's); token_minmax is completion's to add (the dataset's
        # table).
        sizes = TOKEN_STACKS[mt][0]
        missing = [k for k in sizes.missing(arch) if k != "token_minmax"]
        if missing:
            errors.append(
                ("bad-arch", f"model_type={mt} needs Architecture.{'/'.join(missing)}")
            )
        return
    radius, num_radial = arch.get("radius"), arch.get("num_radial")
    if not isinstance(radius, (int, float)) or radius <= 0:
        errors.append(
            ("bad-arch", f"model_type={mt} needs Architecture.radius > 0 (the cutoff)")
        )
    if not isinstance(num_radial, int) or num_radial < 1:
        errors.append(
            (
                "bad-arch",
                f"model_type={mt} needs Architecture.num_radial >= 1 (the "
                "number of radial basis functions)",
            )
        )
    if arch.get("periodic_boundary_conditions"):
        errors.append(
            (
                "bad-arch",
                f"model_type={mt} computes r_ij = pos[j] - pos[i] inside the "
                "step; under periodic_boundary_conditions an edge across the "
                "cell boundary has another vector — the batch carries no "
                "cell shifts yet",
            )
        )


# ------------------------------------------------------------------- donation
def _check_donation(training, errors):
    if str(training.get("optimizer", "")).upper() == "LBFGS" and int(
        training.get("graph_axis") or 1
    ) > 1:
        errors.append(
            (
                "donation-misuse",
                "Training.optimizer=LBFGS stores the params pytree in its "
                "state (aliased buffers) — the distributed donating step "
                "cannot run; use a first-order optimizer or drop graph_axis",
            )
        )


# ----------------------------------------------------------------- eval_shape
def _derive_model_spec(config, arch, voi, completed, errors, skipped):
    """(input_dim, output_dim, output_type, edge_dim, num_nodes) or None."""
    if completed:
        return (
            int(arch["input_dim"]),
            [int(d) for d in arch["output_dim"]],
            list(arch["output_type"]),
            arch.get("edge_dim"),
            int(arch.get("num_nodes") or 8),
        )
    types = voi.get("type")
    indices = voi.get("output_index")
    inputs = voi.get("input_node_features")
    if not (types and indices is not None and inputs):
        skipped.append("eval_shape: head spec underivable from this config")
        return None
    dims = []
    for t, idx in zip(types, indices):
        table = _get(config, "Dataset", f"{t}_features", "dim")
        if not isinstance(table, list) or not (0 <= int(idx) < len(table)):
            skipped.append(
                "eval_shape: Dataset descriptors do not cover the head spec"
            )
            return None
        dims.append(int(table[int(idx)]))
    edge_features = arch.get("edge_features")
    if edge_features:
        edge_dim = len(edge_features)
    elif arch.get("model_type") == "CGCNN":
        edge_dim = 0
    else:
        edge_dim = None
    return len(inputs), dims, list(types), edge_dim, int(arch.get("num_nodes") or 8)


def _check_shapes(config, arch, voi, training, mode, completed, errors, skipped):
    spec = _derive_model_spec(config, arch, voi, completed, errors, skipped)
    if spec is None:
        return None
    input_dim, output_dim, output_type, edge_dim, num_nodes = spec

    t0 = time.perf_counter()
    import jax
    import numpy as np

    from ..models.create import create_model_config, make_example_batch

    arch2 = dict(arch)
    arch2.update(
        input_dim=input_dim,
        output_dim=output_dim,
        output_type=output_type,
        edge_dim=edge_dim,
        num_nodes=num_nodes,
    )
    arch2.setdefault("freeze_conv_layers", False)
    # What completion reads from the dataset's table (utils/config_utils.py
    # _stage_classification_heads); shapes do not depend on the values.
    target_dim = list(arch2.get("target_dim") or output_dim)
    kinds = list(voi.get("loss") or [])
    if not completed and "cross_entropy" in kinds:
        classes = voi.get("num_classes") or []
        if len(kinds) != len(output_dim) or len(classes) != len(output_dim):
            errors.append(
                ("bad-arch", "Variables_of_interest.loss and .num_classes "
                 "name one entry a head")
            )
            return None
        output_dim = [
            int(c) if k == "cross_entropy" else d
            for k, c, d in zip(kinds, classes, output_dim)
        ]
        arch2.update(
            output_dim=output_dim, head_loss=kinds,
            class_minmax=[[0.0, 1.0] if k == "cross_entropy" else None for k in kinds],
        )
    from ..models.families import TOKEN_FAMILIES

    if arch2.get("model_type") in TOKEN_FAMILIES:
        arch2.setdefault("token_minmax", [0.0, 1.0])
    if arch2.get("model_type") == "PNA" and not arch2.get("pna_deg"):
        mn = arch2.get("max_neighbours")
        if mn is None:
            errors.append(
                (
                    "bad-arch",
                    "model_type=PNA needs Architecture.max_neighbours (the "
                    "degree histogram bound) — completion cannot derive "
                    "pna_deg without it",
                )
            )
            return None
        # Flat placeholder histogram: eval_shape only needs pna_deg's
        # PRESENCE — output shapes do not depend on its values.
        arch2["pna_deg"] = [1.0] * (int(mn) + 1)
    try:
        model = create_model_config(config=arch2, verbosity=0)
    except Exception as e:  # noqa: BLE001 — every builder error is a finding
        errors.append(
            ("bad-arch", f"Architecture cannot build a model: {e}")
        )
        return None

    example = make_example_batch(
        input_dim, target_dim, output_type, edge_dim=edge_dim,
        num_nodes=num_nodes, with_positions=model.needs_positions,
    )
    # CSR batch contract (graphs/csr.py): the example batch carries the same
    # row pointers production collation emits — validate length, endpoints,
    # monotonicity, and agreement with the sorted receivers HERE, so a
    # collation/layout regression fails the config gate before any compile.
    from ..graphs.csr import validate_csr

    try:
        validate_csr(
            np.asarray(example.receivers), np.asarray(example.row_ptr),
            example.node_features.shape[0], what="receivers",
        )
        validate_csr(
            np.asarray(example.node_graph), np.asarray(example.graph_ptr),
            example.num_graphs_pad, what="node_graph",
        )
    except ValueError as e:
        errors.append(("shape-mismatch", str(e)))
        return round(time.perf_counter() - t0, 4)
    batch_sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        example,
    )
    key_sds = jax.ShapeDtypeStruct((2,), np.uint32)

    def _trace_serving(batch, key):
        from ..train.trainer import _apply_model

        variables = model.init(
            {"params": key, "dropout": key}, batch, train=False
        )
        return _apply_model(
            model,
            variables["params"],
            variables.get("batch_stats", {}),
            batch,
            train=False,
        )

    # Precision policy (docs/PRECISION.md): with Training.precision="bf16"
    # the gate traces the MIXED-PRECISION step — bf16 compute casts plus the
    # in-jit loss-scale machine — so a dtype bug in a head/loss/optimizer
    # path fails here, not at step 1. The loss-scale state enters as
    # ShapeDtypeStructs (this check must still never allocate device arrays).
    bf16_policy = None
    if mode == "training" and training.get("precision") == "bf16":
        from ..precision.policy import LossScaleConfig

        try:
            bf16_policy = LossScaleConfig.from_config(
                training.get("loss_scale")
            )
        except (TypeError, ValueError):
            bf16_policy = None  # already a bad-precision structural finding

    def _trace_training(batch, key, ls=None):
        from ..train.trainer import _step_body, create_train_state
        from ..utils.optimizer import select_optimizer

        step_model = (
            model.clone(compute_dtype="bfloat16")
            if ls is not None and model.compute_dtype is None
            else model
        )
        variables = step_model.init(
            {"params": key, "dropout": key}, batch, train=False
        )
        # AdamW regardless of Training.optimizer: the shape contract is
        # optimizer-independent (module docstring).
        state = create_train_state(
            step_model, variables, select_optimizer("AdamW", 1e-3)
        )
        if ls is not None:
            state = state.replace(loss_scale=ls)
        new_state, metrics = _step_body(
            step_model,
            select_optimizer("AdamW", 1e-3),
            guard=True,
            loss_scaling=bf16_policy,
        )(state, batch, key)
        return metrics

    try:
        if mode in ("serving", "prediction"):  # forward-only surfaces
            out_shapes = jax.eval_shape(_trace_serving, batch_sds, key_sds)
            _check_output_shapes(
                out_shapes, output_dim, output_type, example, errors
            )
        else:
            if bf16_policy is not None:
                from ..precision.policy import LossScaleState

                ls_sds = LossScaleState(
                    scale=jax.ShapeDtypeStruct((), np.float32),
                    good_steps=jax.ShapeDtypeStruct((), np.int32),
                )
                metrics = jax.eval_shape(
                    _trace_training, batch_sds, key_sds, ls_sds
                )
            else:
                metrics = jax.eval_shape(_trace_training, batch_sds, key_sds)
            loss = metrics["loss"]
            if loss.shape != () or not np.issubdtype(loss.dtype, np.floating):
                errors.append(
                    (
                        "shape-mismatch",
                        f"guarded step loss has shape {loss.shape} dtype "
                        f"{loss.dtype}; expected a floating scalar",
                    )
                )
    except ConfigContractError:
        raise
    except Exception as e:  # noqa: BLE001 — trace errors ARE the findings
        errors.append(
            (
                "shape-mismatch",
                "eval_shape over model+loss+guarded step failed: "
                + str(e).splitlines()[0],
            )
        )
        return round(time.perf_counter() - t0, 4)
    return round(time.perf_counter() - t0, 4)


def _check_output_shapes(out_shapes, output_dim, output_type, example, errors):
    if len(out_shapes) != len(output_dim):
        errors.append(
            (
                "shape-mismatch",
                f"model emits {len(out_shapes)} head(s); config declares "
                f"{len(output_dim)}",
            )
        )
        return
    n_pad = example.node_features.shape[0]
    g_pad = example.num_graphs_pad
    for i, (shape, dim, kind) in enumerate(
        zip(out_shapes, output_dim, output_type)
    ):
        want_rows = g_pad if kind == "graph" else n_pad
        if tuple(shape.shape) != (want_rows, dim):
            errors.append(
                (
                    "shape-mismatch",
                    f"head {i} ({kind}): model emits {tuple(shape.shape)}, "
                    f"config declares ({want_rows}, {dim})",
                )
            )
