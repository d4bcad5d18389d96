"""`dsst` workload subcommands.

Each subcommand is the CLI face of one reference notebook track
(SURVEY.md §3): ``datagen`` replaces the widget-driven generator
notebooks (``group_apply/_resources/01-data-generator.py``), ``forecast``
the scaled fit-tune-score notebook
(``group_apply/02_Fine_Grained_Demand_Forecasting.py:341-556``),
``train`` the distributed-training driver
(``deep_learning/2.distributed-data-loading-petastorm.py:342-470``), and
``hpo`` the data-size playbook (``hyperopt/2. hyperopt on diff sizes of
data.py``). The ``pipeline`` subcommand (the RUNME job-DAG equivalent)
lives in :mod:`.pipeline`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


# --------------------------------------------------------------------------
# datagen
# --------------------------------------------------------------------------

def register_datagen(sub: argparse._SubParsersAction) -> None:
    gen = sub.add_parser(
        "datagen", help="synthetic data generators (demand / bom / regression)"
    )
    gsub = gen.add_subparsers(dest="generator", required=True)

    demand = gsub.add_parser("demand", help="ARMA weekly demand panel → Delta")
    demand.add_argument("--out", required=True, help="Delta table path")
    demand.add_argument("--skus-per-product", type=int, default=10)
    demand.add_argument("--years", type=int, default=3)
    demand.add_argument("--seed", type=int, default=123)
    demand.set_defaults(fn=_cmd_datagen_demand)

    bom = gsub.add_parser("bom", help="random 3-level BoM DAG per SKU → Delta")
    bom.add_argument(
        "--demand", required=True, help="demand Delta table to take SKUs from"
    )
    bom.add_argument("--out", required=True, help="bom Delta table path")
    bom.add_argument("--mapper-out", required=True, help="sku_mapper Delta path")
    bom.add_argument("--depth", type=int, default=3)
    bom.add_argument("--seed", type=int, default=123)
    bom.set_defaults(fn=_cmd_datagen_bom)

    reg = gsub.add_parser(
        "regression", help="byte-targeted synthetic regression → npz"
    )
    reg.add_argument("--bytes", type=float, required=True, dest="n_bytes")
    reg.add_argument("--out", required=True, help="output .npz path")
    reg.set_defaults(fn=_cmd_datagen_regression)

    img = gsub.add_parser(
        "images",
        help="labeled JPEG gratings → Delta (quick-start training data; "
        "each class a distinct orientation/frequency)",
    )
    img.add_argument("--out", required=True, help="Delta table path")
    img.add_argument("--n", type=int, default=1024)
    img.add_argument("--classes", type=int, default=10)
    img.add_argument("--size", type=int, default=64)
    img.add_argument("--seed", type=int, default=0)
    img.add_argument(
        "--label-noise", type=float, default=0.0,
        help="fraction of stored labels replaced by uniform draws; caps "
        "best achievable accuracy at exactly (1-p)+p/classes, making "
        "accuracy curves regression-discriminating",
    )
    img.set_defaults(fn=_cmd_datagen_images)

    ph = gsub.add_parser(
        "photos",
        help="real-photograph JPEG crops (sklearn's CC-BY sample photos) "
        "as an ImageNet-style file tree for dsst ingest",
    )
    ph.add_argument("--out", required=True, help="tree root (files go in Data/)")
    ph.add_argument("--n", type=int, default=192)
    ph.add_argument("--size", type=int, default=96)
    ph.add_argument("--seed", type=int, default=0)
    ph.set_defaults(fn=_cmd_datagen_photos)


def _cmd_datagen_demand(args: argparse.Namespace) -> int:
    # The ARMA sampler runs through JAX; for a datagen-sized workload the
    # host CPU is the right backend — don't claim (or wait on) an
    # accelerator from a data-prep subprocess.
    import jax

    # Ignored by jax once a backend is up in this process: a caller that
    # goes on to use the chip runs this command in a child of its own.
    jax.config.update("jax_platforms", "cpu")

    from ..datagen.demand import DemandConfig, generate_demand, write_demand_delta

    cfg = DemandConfig(
        n_skus_per_product=args.skus_per_product,
        ts_length_years=args.years,
        seed=args.seed,
    )
    df = generate_demand(cfg)
    write_demand_delta(df, args.out)
    print(
        f"demand: {df['SKU'].nunique()} SKUs × "
        f"{df['Date'].nunique()} weeks = {len(df)} rows -> {args.out}"
    )
    return 0


def _cmd_datagen_bom(args: argparse.Namespace) -> int:
    from ..datagen.bom import generate_bom, write_bom_delta

    skus = sorted(set(_read_delta_pandas(args.demand, columns=["SKU"])["SKU"]))
    tables = generate_bom(skus, depth=args.depth, seed=args.seed)
    write_bom_delta(tables, args.out, args.mapper_out)
    print(
        f"bom: {len(tables.bom)} edges, {len(tables.sku_mapper)} sku mappings "
        f"-> {args.out}, {args.mapper_out}"
    )
    return 0


def _cmd_datagen_regression(args: argparse.Namespace) -> int:
    from ..datagen.regression import gen_data
    from ..hpo.shipping import save_shared

    X_train, X_test, y_train, y_test = gen_data(int(args.n_bytes))
    path = save_shared(
        args.out, X_train=X_train, X_test=X_test, y_train=y_train, y_test=y_test
    )
    print(f"regression: {len(X_train)}+{len(X_test)} samples -> {path}")
    return 0


def _cmd_datagen_images(args: argparse.Namespace) -> int:
    from ..datagen.images import write_image_delta

    labels = write_image_delta(
        args.out, args.n, classes=args.classes, size=args.size,
        seed=args.seed, label_noise=args.label_noise, mode="overwrite",
    )
    noise = f", label noise {args.label_noise}" if args.label_noise else ""
    print(
        f"images: {len(labels)} JPEGs, {args.classes} classes, "
        f"{args.size}px{noise} -> {args.out}"
    )
    return 0


def _cmd_datagen_photos(args: argparse.Namespace) -> int:
    from ..datagen.photos import CLASSES, write_photo_tree

    n = write_photo_tree(args.out, args.n, size=args.size, seed=args.seed)
    print(
        f"photos: {n} real-photo JPEG crops, {len(CLASSES)} classes, "
        f"{args.size}px -> {args.out}"
    )
    return 0


# --------------------------------------------------------------------------
# forecast
# --------------------------------------------------------------------------

def register_forecast(sub: argparse._SubParsersAction) -> None:
    fc = sub.add_parser(
        "forecast", help="per-SKU SARIMAX tune + fit + score over a demand table"
    )
    fc.add_argument("--data", required=True, help="demand Delta table")
    fc.add_argument("--out", required=True, help="forecast Delta table to write")
    fc.add_argument(
        "--search", choices=("grid", "tpe"), default="grid",
        help="grid: fuse the full (p,d,q) order grid into chunked "
        "launches with on-device argmin (exact optimum, fewest "
        "launches); tpe: the reference's per-round batched TPE "
        "(compatibility path)",
    )
    fc.add_argument(
        "--chunk-size", type=int, default=None,
        help="groups per grid-fused launch (default: min(G, 64 per "
        "device of the mesh), rounded up to the mesh axis)",
    )
    fc.add_argument("--max-evals", type=int, default=10,
                    help="TPE rounds (--search tpe only)")
    fc.add_argument("--horizon", type=int, default=40, help="holdout weeks")
    fc.add_argument("--rstate", type=int, default=123)
    fc.add_argument(
        "--no-mesh", action="store_true",
        help="keep the group axis on one device (debug)",
    )
    _add_tracking_args(fc, "forecasting")
    fc.add_argument("--max-p", type=int, default=4, help="AR order bound")
    fc.add_argument("--max-d", type=int, default=2, help="differencing bound")
    fc.add_argument("--max-q", type=int, default=4, help="MA order bound")
    fc.add_argument("--max-iter", type=int, default=200, help="Nelder-Mead iters")
    fc.set_defaults(fn=_cmd_forecast)


def _cmd_forecast(args: argparse.Namespace) -> int:
    import pyarrow as pa

    from ..data.delta import write_delta
    from ..ops import SarimaxConfig
    from ..runtime import make_mesh
    from ..workloads.forecasting import (
        EXO_FIELDS,
        add_exo_variables,
        tune_and_forecast_panel,
    )

    t0 = time.perf_counter()
    df = _read_delta_pandas(args.data)
    enriched = add_exo_variables(df)
    mesh = None if args.no_mesh else make_mesh()
    cfg = SarimaxConfig(
        max_p=args.max_p, max_d=args.max_d, max_q=args.max_q,
        k_exog=len(EXO_FIELDS), max_iter=args.max_iter,
    )
    out = tune_and_forecast_panel(
        enriched,
        max_evals=args.max_evals,
        forecast_horizon=args.horizon,
        rstate=args.rstate,
        mesh=mesh,
        cfg=cfg,
        search=args.search,
        chunk_size=args.chunk_size,
    )
    write_delta(
        pa.Table.from_pandas(out, preserve_index=False), args.out, mode="overwrite"
    )
    dt = time.perf_counter() - t0
    err = out["Demand"] - out["Demand_Fitted"]
    mse = float((err**2).mean())
    groups = out.groupby(["Product", "SKU"]).ngroups
    _finish_tracker(
        _open_tracker(args, "forecast"),
        params={"search": args.search, "max_evals": args.max_evals,
                "horizon": args.horizon, "groups": groups},
        metrics={"mse": mse, "wall_s": dt}, step=0,
    )
    print(
        f"forecast: {groups} groups, {len(out)} rows, mse {mse:.2f}, "
        f"{dt:.1f}s -> {args.out}"
    )
    return 0


# --------------------------------------------------------------------------
# eda (single-SKU model selection)
# --------------------------------------------------------------------------

def register_eda(sub: argparse._SubParsersAction) -> None:
    eda = sub.add_parser(
        "eda", help="single-SKU model comparison: Holt-Winters vs SARIMAX vs tuned"
    )
    eda.add_argument("--data", required=True, help="demand Delta table")
    eda.add_argument("--product", default=None)
    eda.add_argument("--sku", default=None, help="defaults to the first SKU")
    eda.add_argument("--horizon", type=int, default=40)
    eda.add_argument("--seasonal-periods", type=int, default=52)
    eda.add_argument("--max-evals", type=int, default=10)
    eda.add_argument("--parallelism", type=int, default=10)
    eda.add_argument("--max-iter", type=int, default=200)
    eda.add_argument(
        "--polish", action="store_true",
        help="refine the single-SKU SARIMAX fits with the host-side "
        "float64 polish (closes the f32 unit-root corner)",
    )
    eda.add_argument(
        "--plot", default=None, metavar="PATH",
        help="write the reference-style comparison figure (actual series "
        "+ top models' holdout predictions) to this PNG",
    )
    _add_tracking_args(eda, "eda")
    eda.set_defaults(fn=_cmd_eda)


def _cmd_eda(args: argparse.Namespace) -> int:
    from ..ops import SarimaxConfig
    from ..workloads.eda import run_eda
    from ..workloads.forecasting import EXO_FIELDS

    df = _read_delta_pandas(args.data)
    tracker = _open_tracker(args, "eda")
    report = run_eda(
        df,
        product=args.product,
        sku=args.sku,
        horizon=args.horizon,
        seasonal_periods=args.seasonal_periods,
        max_evals=args.max_evals,
        parallelism=args.parallelism,
        cfg=SarimaxConfig(k_exog=len(EXO_FIELDS), max_iter=args.max_iter),
        polish=args.polish,
        return_curves=args.plot is not None,
        tracker=tracker,
    )
    print(f"EDA for Product={report.product} SKU={report.sku} "
          f"(holdout {args.horizon} weeks)")
    print(report.scores.to_string(index=False))
    print(f"best SARIMAX order: {report.best_order} (mse {report.best_order_mse:.2f})")
    _finish_tracker(
        tracker,
        params={"product": report.product, "sku": report.sku,
                "max_evals": args.max_evals, "horizon": args.horizon},
        metrics={"best_order_mse": report.best_order_mse},
        step=args.max_evals,
    )
    if args.plot:
        report.plot(args.plot)
        print(f"comparison figure -> {args.plot}")
    return 0


# --------------------------------------------------------------------------
# ingest
# --------------------------------------------------------------------------

def register_ingest(sub: argparse._SubParsersAction) -> None:
    ing = sub.add_parser(
        "ingest", help="image dataset directory → Delta table with stable ids"
    )
    ing.add_argument("--data-root", required=True)
    ing.add_argument("--out", required=True, help="Delta table path")
    ing.add_argument("--pattern", default="*.JPEG")
    ing.add_argument(
        "--label-from", choices=["path", "annotation"], default="path"
    )
    ing.add_argument("--rows-per-fragment", type=int, default=1024)
    ing.add_argument("--append", action="store_true")
    ing.add_argument(
        "--allow-unlabeled", action="store_true",
        help="ingest rows with no determinable label as label_index=-1 "
        "instead of failing (filter them before training)",
    )
    ing.set_defaults(fn=_cmd_ingest)


def _cmd_ingest(args: argparse.Namespace) -> int:
    from ..ingest import ingest_image_dataset

    table = ingest_image_dataset(
        args.data_root,
        args.out,
        file_pattern=args.pattern,
        label_from=args.label_from,
        rows_per_fragment=args.rows_per_fragment,
        mode="append" if args.append else "overwrite",
        on_missing_label="keep" if args.allow_unlabeled else "error",
    )
    print(f"ingested {table.num_records()} rows -> {args.out}")
    return 0


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def register_train(sub: argparse._SubParsersAction) -> None:
    tr = sub.add_parser(
        "train", help="data-parallel image-classifier training from a Delta table"
    )
    tr.add_argument("--data", required=True, help="train Delta table (content/label_index)")
    tr.add_argument("--val-data", default=None, help="validation Delta table")
    tr.add_argument("--epochs", type=int, default=2)
    tr.add_argument("--batch-size", type=int, default=212)
    tr.add_argument("--learning-rate", type=float, default=1e-5)
    tr.add_argument(
        "--lr-schedule", choices=["constant", "cosine"], default=None,
        help="constant reproduces the reference recipe (Adam 1e-5, "
        "2...py:383); cosine adds linear warmup to --learning-rate then "
        "cosine decay to 0 over the current run's total steps — the "
        "standard from-scratch ResNet schedule. Default: the value "
        "persisted in the checkpoint dir (a flag-less --resume keeps the "
        "trained schedule's optimizer structure), else constant",
    )
    tr.add_argument(
        "--warmup-steps", type=int, default=None,
        help="warmup length for --lr-schedule cosine (default: 5%% of "
        "total steps)",
    )
    tr.add_argument("--num-classes", type=int, default=1000)
    tr.add_argument("--crop", type=int, default=224)
    tr.add_argument(
        "--model",
        choices=["resnet50", "tiny", "tiny-bottleneck", "vit-t16",
                 "vit-s16", "vit-tiny"],
        default="resnet50",
    )
    tr.add_argument(
        "--pretrained", default=None, metavar="PATH",
        help="torchvision-layout state dict (.pt/.pth/.npz) to fine-tune "
        "from instead of cold-starting (reference 2...py:150); builds the "
        "model with torch_padding=True for numerical parity; a head whose "
        "class count differs from --num-classes is freshly initialized",
    )
    tr.add_argument(
        "--torch-padding", action=argparse.BooleanOptionalAction, default=None,
        help="force torchvision-style symmetric stride-2 padding (or "
        "--no-torch-padding to force it off); needed when resuming a "
        "--pretrained run without re-passing --pretrained (the "
        "checkpoint's BatchNorm statistics embed the padding choice); "
        "default: True with --pretrained, else the value persisted in "
        "the checkpoint dir, else False",
    )
    tr.add_argument(
        "--fused-bn", action=argparse.BooleanOptionalAction, default=True,
        help="fused BN+relu(+residual) with a minimal-residual custom "
        "VJP (ops/fused_norm.py): same math and parameter tree, ~30%% "
        "fewer HBM bytes per step — the v5e throughput lever. "
        "--no-fused-bn falls back to flax BatchNorm",
    )
    tr.add_argument(
        "--pallas-fused", action="store_true",
        help="second byte lever on top of --fused-bn (bottleneck models "
        "only): the middle BN's apply fused into the 1x1 conv as a "
        "Pallas matmul prologue (ops/fused_matmul.py) — the normalized "
        "activation never exists in HBM; same parameter tree, "
        "single-chip training path",
    )
    tr.add_argument(
        "--eval-topk", type=int, nargs="*", default=[],
        help="extra top-k val accuracies (e.g. --eval-topk 5 adds "
        "val_top5_acc, the standard ImageNet companion metric)",
    )
    tr.add_argument(
        "--augment", action="store_true",
        help="on-device train-time RandomResizedCrop + horizontal flip "
        "inside the jitted step (data/augment.py): the reference's "
        "torchvision train transform, run on the chip instead of host "
        "decode workers; keyed by the training step, so resume replays "
        "the identical crop schedule. Eval/predict never augment",
    )
    tr.add_argument("--workers", type=int, default=2)
    tr.add_argument("--queue-size", type=int, default=20)
    tr.add_argument(
        "--feeder-depth", type=int, default=2,
        help="bound of the background feeder's on-device batch queue "
        "(host-side shard + transfer overlaps step dispatch; HBM held "
        "is depth extra batches). Occupancy/stall are exposed as "
        "feeder_* series on /metrics and in dsst telemetry",
    )
    tr.add_argument(
        "--shard-opt-state", action="store_true",
        help="ZeRO-1: shard optimizer state over the data axis instead of "
        "replicating it (same math, ~world-size less optimizer memory)",
    )
    tr.add_argument(
        "--image-dtype", choices=["float32", "uint8"], default="float32",
        help="uint8 ships raw quantized bytes to the device (4x less host "
        "RAM / queue memory / transfer) and normalizes inside the jitted "
        "step; float32 normalizes on the host (torchvision parity)",
    )
    tr.add_argument(
        "--decode-backend", choices=["auto", "native", "pil"], default="auto",
        help="JPEG decode path: the C++ pool, pure-PIL, or auto (native "
        "when it compiles, per-image PIL fallback); the resolved backend "
        "is reported in the run summary",
    )
    tr.add_argument(
        "--fast-decode", action="store_true",
        help="DCT-domain scaled decode for large sources (PIL draft-mode "
        "equivalent; native backend only): ~2x decode throughput at "
        "2048px sources, pixel values slightly off full-decode parity",
    )
    tr.add_argument(
        "--on-decode-error", choices=["raise", "substitute"], default="raise",
        help="substitute: a corrupt record becomes a zero image (tallied "
        "in the run summary) instead of stopping the epoch — lets a "
        "multi-hour run survive isolated data corruption",
    )
    tr.add_argument(
        "--shuffle", action=argparse.BooleanOptionalAction, default=True,
        help="shuffle row groups per epoch (seeded); --no-shuffle gives "
        "every table pass the identical batch order — what makes a "
        "killed-and-auto-resumed run bitwise-reproduce an uninterrupted "
        "one (the dsst chaos invariant)",
    )
    tr.add_argument("--limit-val-batches", type=int, default=5)
    tr.add_argument("--checkpoint-dir", default=None)
    tr.add_argument("--resume", action="store_true")
    _add_resume_auto_arg(tr)
    tr.add_argument("--profile-dir", default=None)
    _add_health_args(tr)
    _add_tracking_args(tr, "imagenet")
    tr.add_argument(
        "--coordinator", default=None,
        help="host:port for multi-host rendezvous (process 0)",
    )
    tr.set_defaults(fn=_cmd_train)


def _cmd_train(args: argparse.Namespace) -> int:
    import optax

    from ..data import DeltaTable, batch_loader
    from ..data.transform import imagenet_transform_spec
    from ..parallel import ClassifierTask, Trainer, TrainerConfig
    from ..runtime import initialize_distributed, local_topology, make_mesh

    if getattr(args, "pallas_fused", False):
        if not args.fused_bn:
            print("--pallas-fused builds on the fused path; drop "
                  "--no-fused-bn")
            return 1
        if args.model not in ("resnet50", "tiny-bottleneck"):
            # ViT has no BN (the flag would be silently inert); basic-
            # block ResNets have no 1x1 site (the model would raise a
            # deep flax traceback).  Loud and early instead.
            print("--pallas-fused applies to bottleneck ResNets only "
                  "(resnet50, tiny-bottleneck); drop the flag for "
                  f"--model {args.model}")
            return 1
        # Scoring paths map this back to the (math-identical) HLO fused
        # model via resolve_checkpoint's bool(); training uses the
        # Pallas prologue-fused program.  (The multi-chip guard runs
        # AFTER initialize_distributed below: touching the backend here
        # would break jax.distributed.initialize, and the pre-init
        # local count is the wrong topology anyway.)
        args.fused_bn = "pallas"

    initialize_distributed(coordinator_address=args.coordinator)
    # Each process reads a disjoint shard (the reference's
    # cur_shard=rank / shard_count=WORLD, 2...py:249-250); the mesh
    # assembles per-process rows into the global batch.
    topo = local_topology()

    if args.fused_bn == "pallas":
        import jax

        if (topo.global_device_count > 1
                and jax.devices()[0].platform != "cpu"):
            # Compiled pallas_call has no GSPMD partitioning rule yet —
            # multi-chip would compile-error or replicate the batch.
            # (CPU interpret mode lowers to plain HLO, which GSPMD
            # partitions fine — the simulated-mesh CI path.)
            print("--pallas-fused is single-chip for now; use plain "
                  "--fused-bn for multi-chip training")
            return 1

    table = DeltaTable(args.data)
    rows = table.num_records()
    spec = imagenet_transform_spec(
        crop=args.crop, backend=args.decode_backend,
        output_dtype=args.image_dtype, on_error=args.on_decode_error,
        fast_decode=args.fast_decode,
    )
    # Pretrained torchvision weights embed symmetric stride-2 padding in
    # their BatchNorm statistics; the model must match (models/pretrained.py).
    # The choice is persisted next to the checkpoint so a later --resume
    # that omits both flags still rebuilds the same architecture.
    meta_path = (
        Path(args.checkpoint_dir) / "dsst_model.json"
        if args.checkpoint_dir
        else None
    )
    # One read; merged (not replaced) on rewrite so a resume whose --data
    # table carries no labels.json keeps the persisted label_names.
    meta = (
        json.loads(meta_path.read_text())
        if meta_path is not None and meta_path.exists()
        else {}
    )
    if args.torch_padding is not None:
        torch_padding = args.torch_padding
    elif args.pretrained:
        torch_padding = True
    else:
        torch_padding = bool(meta.get("torch_padding", False))
    # Same steps/epoch arithmetic the Trainer uses (rows // global
    # batch), so a fresh cosine trajectory matches the run length.
    steps_per_epoch = rows // (args.batch_size * topo.process_count)
    lr = _resolve_lr_schedule(
        args, meta, total_steps=steps_per_epoch * args.epochs
    )
    meta.update(
        torch_padding=torch_padding,
        model=args.model,
        num_classes=args.num_classes,
        crop=args.crop,
        fused_bn=args.fused_bn,
    )
    # Tables from dsst ingest carry their label vocabulary; persist
    # it WITH the checkpoint (position = model output index), so
    # predict names classes by the vocabulary the model was trained
    # on — never by whatever table it later scores.
    train_labels = Path(args.data) / "labels.json"
    if train_labels.exists():
        vocab = json.loads(train_labels.read_text())
        names = [None] * args.num_classes
        for name, idx in vocab.items():
            if 0 <= int(idx) < args.num_classes:
                names[int(idx)] = name
        meta["label_names"] = names
    if meta_path is not None and topo.process_index == 0:
        meta_path.parent.mkdir(parents=True, exist_ok=True)
        meta_path.write_text(json.dumps(meta))
    model = _build_classifier_model(
        args.model, num_classes=args.num_classes, torch_padding=torch_padding,
        fused_bn=args.fused_bn,
    )
    for k in args.eval_topk:
        # Fail BEFORE training, not at the first eval a whole epoch in.
        if not 1 <= k <= args.num_classes:
            raise SystemExit(
                f"--eval-topk {k} must be in [1, num_classes="
                f"{args.num_classes}]"
            )
    augment = None
    if args.augment:
        from ..data.augment import AugmentConfig

        augment = AugmentConfig()
    task = ClassifierTask(model=model, tx=optax.adam(lr), augment=augment,
                          eval_topk=tuple(args.eval_topk))

    init_state = None
    if args.pretrained and (args.resume_auto or not _has_checkpoint(args)):
        # With --resume and an existing checkpoint the restore would
        # overwrite these weights anyway — skip the conversion. Under
        # --resume-auto the conversion must happen regardless: when
        # every step on disk turns out torn, the trainer falls back to
        # a FRESH start, and that start must be the requested
        # pretrained weights, not a silent random init (a successful
        # restore still overwrites them, costing only the conversion).
        if args.model.startswith("vit"):
            from ..models.pretrained import load_pretrained_vit as _load
        else:
            from ..models.pretrained import load_pretrained_resnet as _load

        variables = _load(args.pretrained, model, image_size=args.crop)
        init_state = task.state_from_variables(variables)

    _mark_interrupted_predecessors(args)
    tracker = _open_tracker(args, "train")
    if tracker is not None:
        tracker.log_params(_args_params(args))

    health_cfg, quarantine = _health_config(args)
    trainer = Trainer(
        TrainerConfig(
            max_epochs=args.epochs,
            total_train_rows=rows,
            limit_val_batches=args.limit_val_batches,
            checkpoint_dir=args.checkpoint_dir,
            resume=args.resume,
            resume_auto=args.resume_auto,
            profile_dir=args.profile_dir,
            shard_opt_state=args.shard_opt_state,
            feeder_depth=args.feeder_depth,
            health=health_cfg,
        ),
        mesh=make_mesh(),
        tracker=tracker,
    )

    val_factory = None
    if args.val_data:
        val_table = DeltaTable(args.val_data)

        def val_factory():
            return batch_loader(
                val_table, batch_size=args.batch_size, num_epochs=1,
                transform_spec=spec, shuffle_row_groups=False,
                cur_shard=topo.process_index, shard_count=topo.process_count,
            ).__enter__()

    from ..resilience.health import TrainingHealthError

    with batch_loader(
        table,
        batch_size=args.batch_size,
        num_epochs=None,
        workers_count=args.workers,
        results_queue_size=args.queue_size,
        transform_spec=spec,
        shuffle_row_groups=args.shuffle,
        cur_shard=topo.process_index,
        shard_count=topo.process_count,
        # Under supervision, the reader tags every batch with its row
        # provenance (so a discarded step quarantines exact rows),
        # consults the blocklist, and survives corrupt samples by
        # quarantining them instead of dying.
        quarantine=quarantine,
        emit_provenance=health_cfg is not None,
        on_corrupt="quarantine" if health_cfg is not None else "raise",
    ) as train_reader:
        try:
            result = trainer.fit(
                task, train_reader, val_data_factory=val_factory,
                state=init_state,
            )
        except TrainingHealthError as e:
            # Operator-facing abort: a clean machine-parseable line (the
            # bundle has the forensics), FAILED run status, exit 3.
            fail_active_tracker()
            print(json.dumps({
                "aborted": True,
                "reason": str(e),
                "diagnostic_bundle": e.bundle_path,
                "quarantine_file": (
                    str(quarantine.path) if quarantine is not None else None
                ),
            }))
            return 3

    last = result.history[-1] if result.history else {}
    # Epoch metrics were logged by the Trainer as they happened; the
    # close prints the "run ->" pointer BEFORE the JSON summary so the
    # last stdout line stays machine-parseable.
    _finish_tracker(tracker)
    print(
        json.dumps(
            {
                "steps": int(result.state.step),
                "epochs": len(result.history),
                "images_per_sec": round(last.get("images_per_sec", 0.0), 2),
                "train_loss": last.get("train_loss"),
                "val_acc": last.get("val_acc"),
                # --eval-topk metrics surface in the summary too.
                **{f"val_top{k}_acc": last.get(f"val_top{k}_acc")
                   for k in args.eval_topk},
                "best_checkpoint": result.best_checkpoint_path,
                "decode_backend": spec.backend,
                "decode_substitutions": spec.substitutions.count,
                # True when a SIGTERM (spot/TPU-VM eviction) cut the run
                # short; rerun with --resume to continue from the saved step.
                "preempted": result.preempted,
                # True when --resume-auto actually RESTORED a prior
                # checkpoint (the Trainer's verdict) — False when it
                # started fresh, including the found-only-wreckage
                # fallback; operators must be able to trust this flag.
                "auto_resumed": result.auto_resumed,
                # Health-supervisor accounting (0s with --health-policy off).
                **(
                    {
                        "skipped_steps": result.skipped_steps,
                        "health_rollbacks": result.health_rollbacks,
                        "quarantined": (
                            len(quarantine) if quarantine is not None else 0
                        ),
                    }
                    if health_cfg is not None else {}
                ),
            }
        )
    )
    return 0


def _has_checkpoint(args: argparse.Namespace) -> bool:
    """True when --resume will actually restore something — the same
    orbax ``latest_step()`` predicate Trainer.fit uses, so the two can't
    disagree about whether a restore will happen."""
    if not (
        (args.resume or getattr(args, "resume_auto", False))
        and args.checkpoint_dir
    ):
        return False
    ckpt = Path(args.checkpoint_dir)
    if not ckpt.is_dir():
        return False
    import orbax.checkpoint as ocp

    try:
        return ocp.CheckpointManager(ckpt.absolute()).latest_step() is not None
    except Exception:
        return False


# --------------------------------------------------------------------------
# predict (beyond parity: score a Delta table with a trained checkpoint)
# --------------------------------------------------------------------------

def _build_classifier_model(name, **kw):
    from .checkpoints import build_classifier_model

    return build_classifier_model(name, **kw)


def register_predict(sub: argparse._SubParsersAction) -> None:
    pr = sub.add_parser(
        "predict",
        help="classify a Delta table of images with a trained checkpoint "
        "and write predictions to a Delta table",
    )
    pr.add_argument("--data", required=True, help="Delta table (content/label_index)")
    pr.add_argument(
        "--checkpoint-dir", required=True,
        help="a dsst train checkpoint dir (model architecture is read "
        "from its dsst_model.json)",
    )
    pr.add_argument("--out", required=True, help="predictions Delta table")
    pr.add_argument(
        "--step", type=int, default=None,
        help="explicit checkpoint step (default: the best step by the "
        "tracked metric, else the latest)",
    )
    pr.add_argument("--batch-size", type=int, default=64)
    pr.add_argument("--crop", type=int, default=None,
                    help="default: the crop persisted in dsst_model.json, "
                    "else 224")
    pr.add_argument("--decode-backend", choices=["auto", "native", "pil"],
                    default="auto")
    pr.set_defaults(fn=_cmd_predict)


def _checkpoint_task(checkpoint_dir, crop_override=None):
    """CLI face of :func:`..config.checkpoints.resolve_checkpoint`:
    prints the missing-meta diagnosis and returns None (callers just
    ``return 1``); a crop/architecture conflict exits with the message.
    """
    from .checkpoints import resolve_checkpoint

    try:
        return resolve_checkpoint(checkpoint_dir, crop_override)
    except FileNotFoundError as e:
        print(e)
        return None
    except (json.JSONDecodeError, KeyError) as e:
        # Corrupt dsst_model.json (truncated write, foreign file) or one
        # missing a required key: same was-this-written-by-dsst-train
        # diagnosis as a missing meta file, not a raw traceback.
        print(
            f"unreadable model metadata in {checkpoint_dir}/dsst_model.json"
            f" ({type(e).__name__}: {e}) — was this checkpoint written by"
            " `dsst train`?"
        )
        return None
    except ValueError as e:
        raise SystemExit(str(e))


def _cmd_predict(args: argparse.Namespace) -> int:
    import numpy as np
    import pyarrow as pa

    import jax
    import jax.numpy as jnp

    from ..data import DeltaTable, batch_loader, write_delta
    from ..data.transform import imagenet_transform_spec
    from ..parallel import restore_state

    resolved = _checkpoint_task(args.checkpoint_dir, args.crop)
    if resolved is None:
        return 1
    meta, crop, model, task = resolved

    table = DeltaTable(args.data)
    spec = imagenet_transform_spec(crop=crop, backend=args.decode_backend)
    predict = None
    rows_label: list[np.ndarray] = []
    rows_pred: list[np.ndarray] = []
    rows_prob: list[np.ndarray] = []
    state = None
    correct = total = 0
    with batch_loader(
        table, batch_size=args.batch_size, num_epochs=1,
        transform_spec=spec, shuffle_row_groups=False, drop_last=False,
        # One worker: multi-threaded readers stream row groups in
        # ARRIVAL order, which would make the emitted "row" index a lie.
        # With one worker and shuffling off, rows stream in table order.
        workers_count=1,
    ) as reader:
        for batch in reader:
            if predict is None:
                state, step = restore_state(
                    task, batch, args.checkpoint_dir, step=args.step
                )
                # Inference never touches the optimizer; free its memory
                # (the structure-matched restore still had to read it).
                params, batch_stats = state.params, state.batch_stats
                state = None
                variables = {"params": params}
                if batch_stats:  # stat-free models (ViT) have none
                    variables["batch_stats"] = batch_stats
                from .checkpoints import make_scorer

                # The SAME jitted scorer dsst serve uses — parity by
                # construction, not by parallel maintenance.
                predict = make_scorer(task, variables)

            pred, prob = predict(batch["image"])
            pred, prob = np.asarray(pred), np.asarray(prob)
            labels = np.asarray(batch["label"])
            rows_label.append(labels)
            rows_pred.append(pred)
            rows_prob.append(prob)
            correct += int((pred == labels).sum())
            total += len(pred)

    if total == 0:
        print("no rows to score")
        return 1
    preds = np.concatenate(rows_pred).astype(np.int64)
    columns = {
        "row": pa.array(np.arange(total, dtype=np.int64)),
        "label_index": pa.array(np.concatenate(rows_label).astype(np.int64)),
        "pred_index": pa.array(preds),
        "pred_prob": pa.array(np.concatenate(rows_prob).astype(np.float64)),
    }
    # Map indices to names via the vocabulary persisted WITH the
    # checkpoint at train time (the reference's predictions are wnid
    # strings for the same reason). Deliberately NOT the scoring table's
    # labels.json: a different table's first-encounter order would
    # silently mislabel.
    names = meta.get("label_names")
    if names:
        columns["pred_label"] = pa.array(
            [names[i] if 0 <= i < len(names) else None for i in preds],
            type=pa.string(),
        )
    out_table = pa.table(columns)
    write_delta(out_table, args.out)
    print(
        json.dumps(
            {
                "rows": total,
                "checkpoint_step": step,
                "accuracy_vs_label_index": round(correct / total, 4),
                "out": str(args.out),
            }
        )
    )
    return 0


# --------------------------------------------------------------------------
# lm (beyond parity: transformer LM on the same Trainer machinery)
# --------------------------------------------------------------------------

def register_lm(sub: argparse._SubParsersAction) -> None:
    lm = sub.add_parser(
        "lm",
        help="train a Transformer LM on a synthetic Markov token stream "
        "(flash attention; optional expert-parallel MoE FFN)",
    )
    lm.add_argument("--vocab", type=int, default=256)
    lm.add_argument("--dim", type=int, default=128)
    lm.add_argument("--heads", type=int, default=4)
    lm.add_argument("--layers", type=int, default=2)
    lm.add_argument("--seq", type=int, default=128)
    lm.add_argument("--batch-size", type=int, default=8)
    lm.add_argument("--epochs", type=int, default=2)
    lm.add_argument("--steps-per-epoch", type=int, default=50)
    lm.add_argument("--learning-rate", type=float, default=3e-4)
    lm.add_argument(
        "--attention", choices=["flash", "reference"], default="flash",
        help="single-chip attention backend; the sequence-parallel ring "
        "path is exercised via the API / driver dry run (it needs a "
        "sequence-sharded mesh, not a batch-sharded one)",
    )
    lm.add_argument(
        "--ffn", choices=["dense", "moe"], default="dense",
        help="moe swaps every block's MLP for a top-1 routed "
        "mixture-of-experts (models/moe.py) with the load-balance aux "
        "loss folded into the objective; experts are sharded over the "
        "mesh (EP) when the device count divides --num-experts, else "
        "replicated",
    )
    lm.add_argument("--num-experts", type=int, default=8)
    lm.add_argument("--aux-loss-weight", type=float, default=0.01)
    lm.add_argument(
        "--concentration", type=float, default=0.05,
        help="Dirichlet concentration of the Markov source's transition "
        "rows; lower = more predictable = lower entropy floor",
    )
    lm.add_argument("--seed", type=int, default=0)
    lm.add_argument("--limit-val-batches", type=int, default=5)
    lm.add_argument(
        "--sample", type=int, default=0, metavar="N",
        help="after training, greedy-generate N tokens from the trained "
        "model (KV-cached decode) and report the mean TRUE-chain "
        "probability of the generated transitions - an end-to-end "
        "sanity number (uniform chance is 1/vocab)",
    )
    lm.add_argument(
        "--lr-schedule", choices=["constant", "cosine"], default=None,
        help="cosine: linear warmup then cosine decay to 0 over the "
        "run's total steps. Default: the value persisted in the "
        "checkpoint dir (flag-less --resume keeps the trained "
        "schedule's optimizer structure), else constant",
    )
    lm.add_argument(
        "--warmup-steps", type=int, default=None,
        help="warmup length for --lr-schedule cosine (default: 5%% of "
        "total steps)",
    )
    lm.add_argument("--checkpoint-dir", default=None)
    lm.add_argument("--resume", action="store_true")
    _add_resume_auto_arg(lm)
    lm.add_argument(
        "--feeder-depth", type=int, default=2,
        help="bound of the background feeder's on-device batch queue "
        "(see dsst train --feeder-depth)",
    )
    _add_health_args(lm)
    _add_tracking_args(lm, "lm")
    lm.add_argument(
        "--coordinator", default=None,
        help="host:port for multi-host rendezvous (process 0)",
    )
    lm.set_defaults(fn=_cmd_lm)


def _cmd_lm(args: argparse.Namespace) -> int:
    import optax

    from ..datagen.tokens import TokenStreamConfig, entropy_floor, token_batches
    from ..models import TransformerLM
    from ..parallel import LMTask, Trainer, TrainerConfig
    from ..runtime import initialize_distributed, local_topology, make_mesh

    initialize_distributed(coordinator_address=args.coordinator)
    topo = local_topology()

    stream = TokenStreamConfig(
        vocab_size=args.vocab,
        batch_size=args.batch_size,
        seq_len=args.seq,
        concentration=args.concentration,
        seed=args.seed,
    )
    floor = entropy_floor(stream)

    mesh = make_mesh()
    # Expert parallelism rides the same devices as DP: expert-dimension
    # operands are sharding-constrained over the "data" axis when the
    # expert count divides it (models/moe.py inserts the all-to-alls).
    n_dev = mesh.shape["data"]
    shard_experts = (
        args.ffn == "moe" and n_dev > 1 and args.num_experts % n_dev == 0
    )
    model = TransformerLM(
        vocab_size=args.vocab,
        dim=args.dim,
        num_heads=args.heads,
        num_layers=args.layers,
        max_seq=args.seq,
        attention=args.attention,
        ffn=args.ffn,
        num_experts=args.num_experts if args.ffn == "moe" else 0,
        expert_mesh=mesh if shard_experts else None,
        expert_axis="data",
    )
    # Schedule trajectory persists beside the checkpoint and resolves
    # exactly like dsst train's (shared _resolve_lr_schedule).
    lm_meta_path = (
        Path(args.checkpoint_dir) / "dsst_lm.json"
        if args.checkpoint_dir
        else None
    )
    lm_meta = (
        json.loads(lm_meta_path.read_text())
        if lm_meta_path is not None and lm_meta_path.exists()
        else {}
    )
    lr = _resolve_lr_schedule(
        args, lm_meta, total_steps=args.steps_per_epoch * args.epochs
    )
    if lm_meta_path is not None and topo.process_index == 0:
        lm_meta_path.parent.mkdir(parents=True, exist_ok=True)
        lm_meta_path.write_text(json.dumps(lm_meta))
    task = LMTask(
        model=model,
        tx=optax.adam(lr),
        aux_loss_weight=args.aux_loss_weight if args.ffn == "moe" else 0.0,
    )

    _mark_interrupted_predecessors(args)
    tracker = _open_tracker(args, "lm")
    if tracker is not None:
        tracker.log_params(_args_params(args))
        tracker.log_params({"entropy_floor": floor})

    health_cfg, quarantine = _health_config(args)
    trainer = Trainer(
        TrainerConfig(
            max_epochs=args.epochs,
            steps_per_epoch=args.steps_per_epoch,
            limit_val_batches=args.limit_val_batches,
            checkpoint_dir=args.checkpoint_dir,
            resume=args.resume,
            resume_auto=args.resume_auto,
            feeder_depth=args.feeder_depth,
            health=health_cfg,
        ),
        mesh=mesh,
        tracker=tracker,
    )

    from ..resilience.health import TrainingHealthError

    # Per-process sample seeds: every host draws a DISJOINT trajectory of
    # the SAME chain (the multi-host analogue of cur_shard/shard_count —
    # without it each process would train on identical batches and the
    # global batch would carry no extra information). Eval rides a third
    # seed range, shared across processes.
    try:
        result = trainer.fit(
            task,
            token_batches(
                stream, sample_seed=args.seed + 1 + topo.process_index
            ),
            val_data_factory=lambda: token_batches(
                stream, num_batches=args.limit_val_batches,
                sample_seed=args.seed + 100_000,
            ),
        )
    except TrainingHealthError as e:
        fail_active_tracker()
        print(json.dumps({
            "aborted": True,
            "reason": str(e),
            "diagnostic_bundle": e.bundle_path,
        }))
        return 3
    _finish_tracker(tracker)
    last = result.history[-1] if result.history else {}
    summary = {
        "steps": int(result.state.step),
        "train_loss": last.get("train_loss"),
        "val_loss": last.get("val_loss"),
        "val_ppl": last.get("val_ppl"),
        "entropy_floor_nats": round(floor, 4),
        "best_checkpoint": result.best_checkpoint_path,
    }
    if args.health_policy != "off":
        summary["skipped_steps"] = result.skipped_steps
        summary["health_rollbacks"] = result.health_rollbacks
    if args.sample > 0:
        # KV-cached greedy decode from the trained weights; scored
        # against the TRUE chain (the generator is the fixture, so the
        # sampled continuation has a computable quality number).
        import numpy as np

        import jax.numpy as jnp

        from ..datagen.tokens import transition_matrix
        from ..models import generate

        if args.seq <= 4:
            raise SystemExit(
                "--sample needs --seq > 4 (4 prompt tokens + at least "
                "one generated token must fit in max_seq)"
            )
        first = next(token_batches(
            stream, num_batches=1, sample_seed=args.seed + 200_000
        ))
        prompt = jnp.asarray(first["tokens"][:1, :4], jnp.int32)
        n = min(args.sample, args.seq - 4)
        if n < args.sample:
            summary["sample_truncated_to"] = n
        out = np.asarray(generate(
            model, {"params": result.state.params}, prompt, n_tokens=n
        ))
        t = transition_matrix(stream)
        probs = [
            float(t[int(out[0, i]), int(out[0, i + 1])])
            for i in range(3, out.shape[1] - 1)
        ]
        summary["sample_tokens"] = out[0].tolist()
        summary["sample_mean_true_prob"] = round(float(np.mean(probs)), 4)
        summary["sample_chance_prob"] = round(1.0 / args.vocab, 4)
    print(json.dumps(summary))
    return 0


# --------------------------------------------------------------------------
# hpo (the data-size playbook demo)
# --------------------------------------------------------------------------

def register_hpo(sub: argparse._SubParsersAction) -> None:
    hp_ = sub.add_parser(
        "hpo", help="distributed TPE sweep over a Lasso objective (size playbook)"
    )
    hp_.add_argument(
        "--data", default=None,
        help=".npz from `datagen regression` (shared-FS shipping); "
        "omit to generate in-process (closure shipping)",
    )
    hp_.add_argument("--bytes", type=float, default=1e6, dest="n_bytes")
    hp_.add_argument("--parallelism", type=int, default=2)
    hp_.add_argument("--max-evals", type=int, default=4)
    hp_.add_argument(
        "--workers", default=None,
        help="comma-separated trial-worker host:port addresses; runs the "
        "sweep over the RPC control plane (requires --data on a path "
        "every worker can read)",
    )
    hp_.add_argument(
        "--secret-file", default=None,
        help="file holding the shared RPC secret (or env DSST_RPC_SECRET); "
        "enables the HMAC handshake with the workers",
    )
    hp_.add_argument(
        "--max-retries", type=int, default=2,
        help="(--workers mode) transport-failure requeues per trial before "
        "it fails; objective exceptions are never retried",
    )
    hp_.add_argument(
        "--resume-auto", action="store_true",
        help="continue a killed sweep: mark this experiment's dead "
        "RUNNING runs INTERRUPTED (journal-based), reload the completed "
        "trials from the newest interrupted run's journal, and run only "
        "the remaining evals (requires tracking enabled)",
    )
    _add_tracking_args(hp_, "hpo")
    hp_.set_defaults(fn=_cmd_hpo)


def _rpc_secret(args: argparse.Namespace) -> bytes | None:
    """Shared RPC secret from --secret-file or env DSST_RPC_SECRET."""
    path = getattr(args, "secret_file", None)
    if path:
        secret = Path(path).read_bytes().strip()
        if not secret:
            raise SystemExit(f"--secret-file {path} is empty")
        return secret
    env = os.environ.get("DSST_RPC_SECRET")
    return env.encode() if env else None


def register_trial_worker(sub: argparse._SubParsersAction) -> None:
    tw = sub.add_parser(
        "trial-worker",
        help="serve HPO trial evaluations for a remote driver (one per host)",
    )
    tw.add_argument(
        "--bind", default="127.0.0.1:0",
        help="host:port to listen on (port 0 = OS-assigned, printed)",
    )
    tw.add_argument(
        "--secret-file", default=None,
        help="file holding the shared RPC secret (or env DSST_RPC_SECRET); "
        "required for non-loopback binds unless --insecure",
    )
    tw.add_argument(
        "--insecure", action="store_true",
        help="allow a non-loopback bind without a secret (trusted isolated "
        "network only; the RPC wire executes pickle on receipt)",
    )
    tw.set_defaults(fn=_cmd_trial_worker)


def _cmd_trial_worker(args: argparse.Namespace) -> int:
    from ..parallel.trials import serve_trial_worker

    serve_trial_worker(
        args.bind,
        block=True,
        secret=_rpc_secret(args),
        allow_insecure=args.insecure,
        # The user (or an orchestrator reading the pipe) needs the
        # OS-assigned port on stdout NOW — serve_forever() never
        # returns, so without the explicit flush a block-buffered pipe
        # would hold the line forever. Library callers get the module
        # logger instead.
        announce=lambda m: print(m, flush=True),
    )
    return 0


def _journaled_trials(root: str, experiment: str) -> list[dict]:
    """Completed trials of ``experiment``'s interrupted runs, rebuilt
    from their journals (``trial`` events) into the fmin store format —
    the resume state for ``dsst hpo --resume-auto``.

    Merged across ALL interrupted runs, newest first per tid: a sweep
    killed twice leaves its early trials journaled in run A and its
    later ones in run B, and progress must compound instead of the
    survivor re-running (and re-journaling) what A already paid for.
    Only the contiguous tid prefix is kept: the async pool may have
    journaled tid 3 while tid 2 died with the process, and the driver
    re-proposes from ``len(trials)`` — a gap would collide.
    """
    from ..tracking import read_journal, sweep_interrupted

    if not Path(root).is_dir():
        return []
    report = sweep_interrupted(root, experiment)
    candidates = sorted(
        (c for c in report if c["effective_status"] == "INTERRUPTED"),
        key=lambda c: c.get("start_time") or 0.0,
        reverse=True,
    )
    by_tid: dict[int, dict] = {}
    sources: list[str] = []
    for c in candidates:
        contributed = False
        for e in read_journal(c["run_dir"]):
            if e.get("event") != "trial" or int(e["tid"]) in by_tid:
                continue
            contributed = True
            by_tid[int(e["tid"])] = {
                "tid": int(e["tid"]),
                "point": dict(e.get("point") or {}),
                "result": {"loss": e.get("loss"),
                           "status": e.get("status")},
                "book_time": e.get("time"),
                "duration": 0.0,
            }
        if contributed:
            sources.append(f"{c['experiment']}/{c['run_id']}")
    trials = []
    for tid in range(len(by_tid)):
        if tid not in by_tid:
            break
        trials.append(by_tid[tid])
    if trials:
        print(
            f"hpo --resume-auto: continuing from {len(trials)} "
            f"journaled trial(s) of {', '.join(sources)}"
        )
    return trials


def _cmd_hpo(args: argparse.Namespace) -> int:
    from ..datagen.regression import gen_data, train_and_eval, tune_alpha
    from ..hpo.shipping import load_shared

    resumed: list[dict] = []
    if args.resume_auto:
        if args.no_tracking or not args.tracking_root:
            print("--resume-auto needs tracking enabled (the run journal "
                  "IS the resume state)")
            return 2
        resumed = _journaled_trials(args.tracking_root, args.experiment)

    if args.workers:
        # Remote mode: objective ships by module reference, data by
        # shared FS — the multi-host SparkTrials shape. Validate BEFORE
        # opening a tracker: a usage error must not litter an orphaned
        # RUNNING run.
        if not args.data:
            print("--workers requires --data (shared-FS npz every worker can read)")
            return 2
        tracker = _open_tracker(args, "hpo")
        import numpy as np

        from ..hpo import fmin, hp
        from ..parallel import HostTrials

        space = {
            "alpha": hp.uniform("alpha", 0.0, 10.0),
            "data_path": hp.choice("data_path", [str(args.data)]),
        }
        trials = HostTrials(
            args.workers.split(","),
            parallelism=args.parallelism,
            secret=_rpc_secret(args),
            max_retries=args.max_retries,
        )
        trials.trials.extend(resumed)
        best = fmin(
            "dss_ml_at_scale_tpu.hpo.objectives:lasso_shared",
            space,
            max_evals=args.max_evals,
            trials=trials,
            rstate=np.random.default_rng(0),
            tracker=tracker,
        )
        ok = sum(1 for t in trials.trials if t["result"]["status"] == "ok")
        _finish_tracker(
            tracker, params={"mode": "remote", "workers": args.workers}
        )
        print(
            f"hpo (remote, {len(trials.workers)} workers): best alpha "
            f"{best['alpha']:.4f} ({ok}/{len(trials.trials)} trials ok)"
        )
        return 0

    tracker = _open_tracker(args, "hpo")
    if args.data:
        arrays = load_shared(args.data)
        data = (
            arrays["X_train"], arrays["X_test"],
            arrays["y_train"], arrays["y_test"],
        )
        mode = "shared-fs"
    else:
        data = gen_data(int(args.n_bytes))
        mode = "closure"

    def objective(alpha):
        return train_and_eval(data, alpha)

    trials = None
    if resumed:
        from ..parallel import DeviceTrials

        trials = DeviceTrials(parallelism=args.parallelism)
        trials.trials.extend(resumed)
    best = tune_alpha(
        objective, parallelism=args.parallelism, max_evals=args.max_evals,
        tracker=tracker, trials=trials,
    )
    _finish_tracker(tracker, params={"mode": mode, "best_alpha": best})
    print(f"hpo ({mode}): best alpha {best:.4f}")
    return 0


# --------------------------------------------------------------------------
# shared helpers
# --------------------------------------------------------------------------

DEFAULT_TRACKING_ROOT = "dsst_runs"


def _add_tracking_args(parser, experiment: str) -> None:
    """Tracking flags with autologging ON by default.

    The reference logs every SparkTrials trial under an active MLflow run
    with zero user code (``hyperopt/1. hyperopt.py:130-136``); the
    equivalent default here is a RunStore under ./dsst_runs unless
    --no-tracking (or --tracking-root '') opts out. The env var
    DSST_TRACKING_ROOT overrides the default root (read per invocation,
    so wrappers and test harnesses can redirect every run — including
    subprocess pipelines — without threading a flag through)."""
    parser.add_argument("--experiment", default=experiment)
    root = os.environ.get("DSST_TRACKING_ROOT", DEFAULT_TRACKING_ROOT)
    parser.add_argument(
        "--tracking-root", default=root,
        help=f"run-store root (default ./{DEFAULT_TRACKING_ROOT}, or env "
        "DSST_TRACKING_ROOT)",
    )
    parser.add_argument(
        "--no-tracking", action="store_true",
        help="disable the default run/trial autologging",
    )


# The one tracker a CLI invocation may have open. cli.main closes it as
# FAILED when a command raises, so a crashed run (bad table, OOM,
# Ctrl-C) never lingers in RUNNING state in the run store.
_active_tracker = None

# The dsst argv of this invocation (cli.main stashes it before
# dispatch): journaled into each run's start event so `dsst runs doctor
# --resume` can re-execute exactly what was interrupted.
_invocation_argv: list[str] | None = None


def set_invocation_argv(argv: list[str] | None) -> None:
    global _invocation_argv
    _invocation_argv = list(argv) if argv is not None else None


def _open_tracker(args: argparse.Namespace, run_name: str):
    """RunStore for a CLI run, or None when tracking is opted out."""
    global _active_tracker
    if getattr(args, "no_tracking", False) or not getattr(
        args, "tracking_root", None
    ):
        return None
    from ..tracking import RunStore, set_run_cmdline

    set_run_cmdline(_invocation_argv)
    _active_tracker = RunStore(
        args.tracking_root, args.experiment, run_name=run_name
    )
    return _active_tracker


def fail_active_tracker() -> None:
    """Close a command's still-open run as FAILED (crash path)."""
    global _active_tracker
    if _active_tracker is not None:
        try:
            _active_tracker.finish("FAILED")
        finally:
            _active_tracker = None


def _args_params(args: argparse.Namespace) -> dict:
    """CLI invocation as loggable run params (internals and Nones dropped)."""
    skip = {"fn", "no_tracking", "tracking_root"}
    return {
        k: v for k, v in vars(args).items() if k not in skip and v is not None
    }


def _add_resume_auto_arg(parser) -> None:
    parser.add_argument(
        "--resume-auto", action="store_true",
        help="crash-only restart: resume from the newest manifest-intact "
        "checkpoint if one exists (falling back past torn steps, "
        "quarantining wreckage, sweeping stranded .tmp files), else "
        "start fresh — never errors on an empty dir and never needs a "
        "step name. Also marks this experiment's dead RUNNING runs "
        "INTERRUPTED (journal-based) before starting. The entry point "
        "watchdogs (`dsst runs doctor --resume`) and the chaos soak use",
    )


def _mark_interrupted_predecessors(args: argparse.Namespace) -> None:
    """--resume-auto's store hygiene: flip this experiment's dead-PID
    RUNNING runs to INTERRUPTED before opening a new run, so the store
    converges without waiting for an explicit doctor sweep."""
    if not getattr(args, "resume_auto", False):
        return
    if getattr(args, "no_tracking", False) or not getattr(
        args, "tracking_root", None
    ):
        return
    from ..tracking import sweep_interrupted

    if Path(args.tracking_root).is_dir():
        sweep_interrupted(args.tracking_root, args.experiment)


def _add_health_args(parser) -> None:
    """Training-health supervisor flags, shared by train and lm."""
    parser.add_argument(
        "--health-policy", choices=["off", "skip", "rollback", "abort"],
        default="off",
        help="supervise every train step with on-device non-finite "
        "(loss/grad-norm isfinite) and EWMA loss-spike detection: a bad "
        "update is discarded before commit and its batch quarantined; "
        "past a --max-consecutive-skips streak, 'skip' aborts (a fully "
        "poisoned stream must not spin) while 'rollback' restores the "
        "newest intact checkpoint (then aborts after --max-rollbacks); "
        "'abort' stops on the first bad step with a diagnostic bundle. "
        "Default off (the unsupervised loop needs no per-step verdict "
        "fetch)",
    )
    parser.add_argument(
        "--spike-zscore", type=float, default=6.0,
        help="loss-spike threshold: |loss - ewma_mean| > Z * ewma_std",
    )
    parser.add_argument(
        "--health-warmup", type=int, default=20,
        help="healthy steps observed before the spike detector arms "
        "(non-finite detection is always armed)",
    )
    parser.add_argument(
        "--max-consecutive-skips", type=int, default=3,
        help="consecutive bad steps tolerated as skips; one more "
        "escalates skip -> rollback (or abort)",
    )
    parser.add_argument(
        "--max-rollbacks", type=int, default=2,
        help="checkpoint rollbacks before the run aborts with a "
        "diagnostic bundle",
    )


def _health_config(args: argparse.Namespace):
    """``(HealthConfig | None, QuarantineList | None)`` from the flags.

    The quarantine blocklist lives next to the checkpoints
    (``<checkpoint_dir>/quarantine.jsonl``) so resume, replay, and
    ``dsst quarantine`` all find it; without a checkpoint dir, bad
    batches are still discarded and counted, just not persisted.
    """
    if getattr(args, "health_policy", "off") == "off":
        return None, None
    from ..resilience.health import HealthConfig
    from ..resilience.rollback import QuarantineList

    quarantine = None
    if getattr(args, "checkpoint_dir", None):
        quarantine = QuarantineList(
            Path(args.checkpoint_dir) / "quarantine.jsonl"
        )
    return HealthConfig(
        policy=args.health_policy,
        spike_zscore=args.spike_zscore,
        warmup_steps=args.health_warmup,
        max_consecutive_skips=args.max_consecutive_skips,
        max_rollbacks=args.max_rollbacks,
        quarantine=quarantine,
    ), quarantine


def _resolve_lr_schedule(args: argparse.Namespace, meta: dict,
                         total_steps: int):
    """Resolve --lr-schedule/--warmup-steps against persisted metadata.

    Returns the optax learning rate (float or schedule) and mutates
    ``meta`` with the full trajectory (lr_schedule, warmup_steps,
    decay_steps). A scheduled adam has a different opt_state STRUCTURE,
    and the restored step count lands ON the schedule curve — so a
    flag-less --resume must rebuild not just a schedule-shaped optimizer
    but the SAME warmup/decay trajectory, or the LR would jump
    discontinuously mid-run. Passing --lr-schedule explicitly redefines
    the trajectory from the current invocation's run length.
    """
    explicit = args.lr_schedule is not None
    schedule = args.lr_schedule if explicit else meta.get(
        "lr_schedule", "constant"
    )
    if schedule != "cosine":
        meta["lr_schedule"] = "constant"
        meta.pop("warmup_steps", None)
        meta.pop("decay_steps", None)
        return args.learning_rate

    import optax

    if explicit or "decay_steps" not in meta:
        decay = max(1, total_steps)
        warmup = (
            args.warmup_steps
            if args.warmup_steps is not None
            else max(1, decay // 20)
        )
    else:
        decay = int(meta["decay_steps"])
        warmup = (
            args.warmup_steps
            if args.warmup_steps is not None
            else int(meta.get("warmup_steps", max(1, decay // 20)))
        )
    warmup = min(warmup, decay)
    meta.update(lr_schedule="cosine", warmup_steps=warmup, decay_steps=decay)
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=args.learning_rate,
        warmup_steps=warmup,
        decay_steps=decay,
    )


def _finish_tracker(tracker, params: dict | None = None,
                    metrics: dict | None = None, step: int | None = None):
    """The one place a CLI run is closed: final params/metrics, the
    telemetry archive (counter snapshot + span JSONL — what `dsst
    telemetry` reads back), FINISHED status, and the 'run ->' pointer
    the user needs to find it."""
    global _active_tracker
    if tracker is None:
        return
    if params:
        tracker.log_params(params)
    if metrics:
        tracker.log_metrics(metrics, step=step)
    from .. import telemetry

    tracker.log_telemetry()
    span_log = telemetry.get_span_log()
    if span_log.events():
        tracker.log_text(span_log.to_jsonl(), "spans.jsonl")
    tracker.finish()
    if tracker is _active_tracker:
        _active_tracker = None
    print(f"run -> {tracker.path}")


def _read_delta_pandas(path: str, columns: list[str] | None = None):
    """Whole-table read through the Delta log (no Spark; reference reads
    the same tables with ``spark.read.format("delta")``)."""
    import pyarrow.parquet as pq

    from ..data.delta import DeltaTable

    table = DeltaTable(path)
    import pyarrow as pa

    parts = [pq.read_table(uri, columns=columns) for uri in table.file_uris()]
    return pa.concat_tables(parts).to_pandas()


def register_export(sub: argparse._SubParsersAction) -> None:
    ex = sub.add_parser(
        "export",
        help="trained checkpoint → torchvision-layout .npz state dict "
        "(readable by torch-ecosystem consumers and by this CLI's own "
        "--pretrained; BN num_batches_tracked is not emitted — use "
        "load_state_dict(strict=False) on the torch side)",
    )
    ex.add_argument("--checkpoint-dir", required=True,
                    help="a dsst train checkpoint dir (dsst_model.json)")
    ex.add_argument("--out", required=True, help=".npz output path")
    ex.add_argument("--step", type=int, default=None,
                    help="explicit checkpoint step (default: best, else latest)")
    ex.set_defaults(fn=_cmd_export)


def _cmd_export(args: argparse.Namespace) -> int:
    import numpy as np

    from ..models.pretrained import export_torchvision
    from ..parallel import restore_state

    if not args.out.endswith(".npz"):
        # export_torchvision also enforces this; failing before the
        # (slow) restore gives the error immediately.
        raise SystemExit(f"--out must end in .npz (got {args.out!r})")
    resolved = _checkpoint_task(args.checkpoint_dir)
    if resolved is None:
        return 1
    _meta, crop, model, task = resolved
    sample = {
        "image": np.zeros((1, crop, crop, 3), np.float32),
        "label": np.zeros((1,), np.int32),
    }
    state, step = restore_state(task, sample, args.checkpoint_dir,
                                step=args.step)
    # Export never touches the optimizer; free its ~2x-params memory
    # before materializing the numpy copies (restore_state's guidance).
    variables = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    state = None
    exported = export_torchvision(variables, model, args.out)
    print(json.dumps({
        "checkpoint_step": step,
        "tensors": len(exported),
        "out": args.out,
    }))
    return 0


def register_serve(sub: argparse._SubParsersAction) -> None:
    sv = sub.add_parser(
        "serve",
        help="HTTP inference server over a trained checkpoint: "
        "GET /healthz + /readyz, POST /predict (raw JPEG body or JSON "
        '{"instances": ["<base64 jpeg>", ...]}); scheduler-mediated '
        "scoring (bounded admission queue, cross-request dynamic "
        "batching into one fixed-shape compiled scorer, graceful "
        "drain), label names from the trained vocabulary",
    )
    sv.add_argument("--checkpoint-dir", required=True,
                    help="a dsst train checkpoint dir (dsst_model.json)")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8008)
    sv.add_argument("--step", type=int, default=None,
                    help="explicit checkpoint step (default: best, else latest)")
    sv.add_argument("--micro-batch", type=int, default=8,
                    help="compiled scoring batch; the batcher coalesces "
                    "waiting images across requests up to it")
    sv.add_argument(
        "--queue-depth", type=int, default=64,
        help="max admitted-but-unscored images; beyond it requests get "
        "429 with a measured Retry-After",
    )
    sv.add_argument(
        "--batch-window-ms", type=float, default=5.0,
        help="max wait for an under-filled batch to gain company — the "
        "latency/throughput dial of the cross-request batcher",
    )
    sv.add_argument(
        "--deadline-ms", type=float, default=2000.0,
        help="per-request deadline: work not scored in time is dropped "
        "with 503 instead of scored late (0 disables)",
    )
    sv.add_argument(
        "--drain-timeout", type=float, default=10.0,
        help="graceful-shutdown bound: seconds to finish queued work "
        "after Ctrl-C before the server closes anyway",
    )
    sv.add_argument(
        "--decode-workers", type=int, default=2,
        help="JPEG decode threads feeding the batcher (host-side work, "
        "off the scoring thread)",
    )
    sv.add_argument(
        "--access-log", default=None, metavar="JSONL",
        help="structured request log: one JSON line per /predict "
        "(request_id matching the X-DSST-Trace response header, "
        "status, queue_ms, batch_fill)",
    )
    sv.set_defaults(fn=_cmd_serve)


def _cmd_serve(args: argparse.Namespace) -> int:
    from ..serving import SchedulerConfig
    from ..workloads.serving import Predictor, serve_in_thread

    # Resolve the metadata FIRST (narrowly scoped corrupt-meta
    # diagnosis, same as predict/export); a KeyError from the much
    # larger Predictor construction below — e.g. an orbax tree that
    # doesn't match the model — must NOT be misattributed to
    # dsst_model.json. The resolved tuple is handed to Predictor so
    # startup resolves the checkpoint exactly once.
    resolved = _checkpoint_task(args.checkpoint_dir)
    if resolved is None:
        return 1
    try:
        predictor = Predictor(args.checkpoint_dir, step=args.step,
                              micro_batch=args.micro_batch,
                              resolved=resolved)
    except FileNotFoundError as e:
        # Missing orbax steps: print the diagnosis and exit like
        # predict/export, no traceback.
        print(e)
        return 1
    config = SchedulerConfig(
        queue_depth=args.queue_depth,
        batch_window_ms=args.batch_window_ms,
        deadline_ms=args.deadline_ms,
        drain_timeout_s=args.drain_timeout,
        decode_workers=args.decode_workers,
    )
    # The accept loop runs in the handle's thread so Ctrl-C lands here,
    # where close() can drain WHILE the server still answers (/readyz
    # flips 503, queued work finishes, in-flight responses complete).
    handle = serve_in_thread(predictor, args.host, args.port, config=config,
                             access_log=args.access_log)
    print(json.dumps({
        "serving": handle.address,
        "model": predictor.meta.get("model"),
        "checkpoint_step": predictor.step,
        "crop": predictor.crop,
        "micro_batch": predictor.micro_batch,
        "queue_depth": config.queue_depth,
        "batch_window_ms": config.batch_window_ms,
        "deadline_ms": config.deadline_ms,
    }), flush=True)
    try:
        while handle.thread.is_alive():
            handle.thread.join(1.0)
    except KeyboardInterrupt:
        print(json.dumps({"draining": True,
                          "pending_images": handle.scheduler.pending}),
              flush=True)
    finally:
        handle.close(args.drain_timeout)
    return 0


def register_serve_lm(sub: argparse._SubParsersAction) -> None:
    sv = sub.add_parser(
        "serve-lm",
        help="HTTP token-streaming LM server: continuous-batching decode "
        "over preallocated KV slots; POST /generate streams one chunked "
        "NDJSON line per token (plus a terminal done-line carrying the "
        "trace id), GET /healthz + /readyz + /slo ride the same "
        "keep-alive handler as dsst serve",
    )
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8008)
    sv.add_argument(
        "--slots", type=int, default=8,
        help="preallocated KV slots — the max generations decoding "
        "concurrently in one slot_decode dispatch",
    )
    sv.add_argument(
        "--max-len", type=int, default=256,
        help="per-slot KV capacity; prompt + max_new_tokens beyond it "
        "is rejected with 400 before admission",
    )
    sv.add_argument(
        "--prefill-buckets", default="16,32,64", metavar="CSV",
        help="padded prompt lengths the prefill program compiles for; "
        "a prompt is padded up to the smallest bucket that fits",
    )
    sv.add_argument(
        "--queue-depth", type=int, default=32,
        help="max admitted-but-unslotted generations; beyond it "
        "requests get 429 with a measured Retry-After",
    )
    sv.add_argument(
        "--deadline-ms", type=float, default=0.0,
        help="per-generation deadline: a slot past it is retired with "
        "a streamed error instead of decoding late (0 disables); also "
        "arms the ttft_p99 SLO budget",
    )
    sv.add_argument(
        "--inter-token-budget-ms", type=float, default=0.0,
        help="arms the inter_token_p99 SLO budget (0 leaves it "
        "informational)",
    )
    sv.add_argument(
        "--drain-timeout", type=float, default=10.0,
        help="graceful-shutdown bound: seconds for in-flight streams "
        "to finish after Ctrl-C before the server closes anyway",
    )
    sv.add_argument(
        "--stub", action="store_true",
        help="serve the deterministic stub decoder instead of a "
        "TransformerLM — the full engine + streaming stack with no "
        "device work (what the chaos/CI harnesses spawn)",
    )
    sv.add_argument(
        "--step-ms", type=float, default=2.0,
        help="stub-only: simulated wall time of one decode step "
        "(charged once per step, not per active slot)",
    )
    sv.add_argument("--vocab", type=int, default=256,
                    help="model/stub vocabulary size")
    sv.add_argument("--dim", type=int, default=128)
    sv.add_argument("--heads", type=int, default=4)
    sv.add_argument("--layers", type=int, default=2)
    sv.add_argument("--attention", choices=["flash", "reference"],
                    default="reference")
    sv.add_argument(
        "--model-config", default=None, metavar="JSON",
        help="architecture file with the published config.json keys; "
        "its model_type picks the model (models/served.py build_served): "
        "jamba the hybrid state-space model (models/jamba.py), nemotron_h "
        "the hybrid Mamba-2 / latent-expert model (models/nemotron_h.py), "
        "mistral4 or none a latent-attention expert model "
        "(models/mla_moe.py); an expert model reads num_layers, "
        "n_routed_experts and vocab_size as held here, router_width and "
        "expert_offset beside them; any other model_type is refused. "
        "Served with random weights through the same decoder and "
        "engine, in place of the TransformerLM that "
        "--vocab/--dim/--heads/--layers size",
    )
    sv.add_argument("--seed", type=int, default=0,
                    help="init seed for the random-weight TransformerLM "
                    "(no LM checkpoint format yet; serving a trained LM "
                    "is gated on the lm checkpoint loader)")
    sv.add_argument(
        "--access-log", default=None, metavar="JSONL",
        help="structured request log: one JSON line per /generate "
        "(request_id matching the X-DSST-Trace header and the "
        "done-line's trace field, status, tokens, ttft_ms)",
    )
    _add_tracking_args(sv, "serve-lm")
    sv.set_defaults(fn=_cmd_serve_lm)


def _cmd_serve_lm(args: argparse.Namespace) -> int:
    from ..serving.lm import LMConfig, LMEngine, StubLMDecoder
    from ..workloads.serving import serve_lm_in_thread

    try:
        buckets = tuple(
            int(b) for b in str(args.prefill_buckets).split(",") if b
        )
        config = LMConfig(
            slots=args.slots,
            max_len=args.max_len,
            prefill_buckets=buckets,
            queue_depth=args.queue_depth,
            deadline_ms=args.deadline_ms,
            inter_token_budget_ms=args.inter_token_budget_ms,
            drain_timeout_s=args.drain_timeout,
        )
    except ValueError as e:
        print(e)
        return 1
    device_facts: dict = {}
    if args.stub:
        decoder = StubLMDecoder(
            vocab_size=args.vocab, step_ms=args.step_ms,
            slots=args.slots, max_len=args.max_len,
            buckets=config.prefill_buckets,
        )
    else:
        import jax
        import jax.numpy as jnp

        from ..models import TransformerLM
        from ..models.served import build_served
        from ..serving.lm import TransformerDecoder

        # Neither tree gets a name here: the decoder keeps its own copy
        # at the widths it multiplies in, and a wider one is freed.
        if args.model_config:
            with open(args.model_config) as f:
                arch = json.load(f)
            try:
                model = build_served(arch, attention=args.attention)
            except ValueError as e:
                print(e)
                return 1
            variables = model.init(jax.random.key(args.seed))
        else:
            model = TransformerLM(
                vocab_size=args.vocab, dim=args.dim, num_heads=args.heads,
                num_layers=args.layers, max_seq=args.max_len,
                attention=args.attention,
            )
            variables = model.init(
                jax.random.key(args.seed),
                jnp.zeros((1, config.prefill_buckets[0]), jnp.int32),
            )
        decoder = TransformerDecoder(
            model, variables,
            slots=args.slots, max_len=args.max_len,
            buckets=config.prefill_buckets,
        )
        del variables
        from ..runtime.compile_cache import enable_compile_cache

        # The boot line names the device the weights sit on, as JAX
        # reports it, and where compiled programs are cached.
        dev = jax.devices()[0]
        device_facts = {
            "model": type(model).__name__,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "compile_cache_dir": enable_compile_cache(),
        }
    # The tracker's journaled start event (pid + boot id) is what lets
    # `dsst runs doctor` classify a SIGKILL'd replica as INTERRUPTED —
    # the chaos drill's whole observability story.
    tracker = _open_tracker(args, "serve-lm")
    if tracker is not None:
        tracker.log_params(_args_params(args))
    engine = LMEngine(decoder, config).start()
    handle = serve_lm_in_thread(engine, args.host, args.port,
                                access_log=args.access_log)
    print(json.dumps({
        "serving": handle.address,
        "port": handle.port,
        "decoder": type(decoder).__name__,
        "slots": config.slots,
        "max_len": config.max_len,
        "prefill_buckets": list(config.prefill_buckets),
        "queue_depth": config.queue_depth,
        "deadline_ms": config.deadline_ms,
        **device_facts,
    }), flush=True)
    try:
        while handle.thread.is_alive():
            handle.thread.join(1.0)
    except KeyboardInterrupt:
        print(json.dumps({"draining": True, "pending": engine.pending}),
              flush=True)
    finally:
        handle.close(args.drain_timeout)
        _finish_tracker(tracker)
    return 0


def register_checkpoints(sub: argparse._SubParsersAction) -> None:
    ck = sub.add_parser(
        "checkpoints",
        help="checkpoint maintenance: verify per-step integrity manifests",
    )
    csub = ck.add_subparsers(dest="checkpoints_cmd", required=True)
    vf = csub.add_parser(
        "verify",
        help="walk a checkpoint dir's steps and report intact / corrupt / "
        "unverified per the dsst_manifest.json content checksums — the "
        "operator-facing face of the restore-fallback integrity layer",
    )
    vf.add_argument("dir", help="a dsst train/lm checkpoint directory")
    vf.add_argument(
        "--json", action="store_true",
        help="emit the full report as one JSON document instead of lines",
    )
    vf.set_defaults(fn=_cmd_checkpoints_verify)


def _cmd_checkpoints_verify(args: argparse.Namespace) -> int:
    from ..resilience import verify_checkpoint_dir

    if not Path(args.dir).is_dir():
        print(f"no such checkpoint directory: {args.dir}")
        return 2
    report = verify_checkpoint_dir(args.dir)
    counts = {"intact": 0, "corrupt": 0, "unverified": 0}
    for entry in report:
        counts[entry["status"]] += 1
    if args.json:
        print(json.dumps({"dir": args.dir, "steps": report, **counts}))
    else:
        if not report:
            print(f"no checkpoint steps under {args.dir}")
        for entry in report:
            line = f"step {entry['step']}: {entry['status']}"
            if entry["problems"]:
                line += " (" + "; ".join(entry["problems"]) + ")"
            print(line)
        if report:
            print(
                f"{counts['intact']} intact, {counts['corrupt']} corrupt, "
                f"{counts['unverified']} unverified (no manifest)"
            )
    return 1 if counts["corrupt"] else 0


def register_quarantine(sub: argparse._SubParsersAction) -> None:
    qr = sub.add_parser(
        "quarantine",
        help="manage the poison-batch blocklist written by the training "
        "health supervisor (rows excluded from replay/resume)",
    )
    qsub = qr.add_subparsers(dest="quarantine_cmd", required=True)

    target_help = (
        "a quarantine .jsonl file, or a checkpoint dir containing "
        "quarantine.jsonl (where `dsst train --health-policy` writes it)"
    )
    ls = qsub.add_parser(
        "list", help="print quarantined row ranges, one JSON line each"
    )
    ls.add_argument("target", help=target_help)
    ls.set_defaults(fn=_cmd_quarantine_list)

    cl = qsub.add_parser(
        "clear",
        help="drop every entry (the rows rejoin the next replay/resume)",
    )
    cl.add_argument("target", help=target_help)
    cl.set_defaults(fn=_cmd_quarantine_clear)


def _quarantine_target(target: str) -> Path:
    p = Path(target)
    return p / "quarantine.jsonl" if p.is_dir() else p


def _cmd_quarantine_list(args: argparse.Namespace) -> int:
    from ..resilience.rollback import QuarantineList

    path = _quarantine_target(args.target)
    if not path.exists():
        print(f"no quarantine list at {path}")
        return 1
    q = QuarantineList(path)
    rows = 0
    for entry in q.entries:
        rows += int(entry["row_hi"]) - int(entry["row_lo"])
        print(json.dumps(entry))
    print(f"{len(q)} entries, {rows} rows quarantined ({path})",
          file=sys.stderr)
    return 0


def _cmd_quarantine_clear(args: argparse.Namespace) -> int:
    from ..resilience.rollback import QuarantineList

    path = _quarantine_target(args.target)
    if not path.exists():
        print(f"no quarantine list at {path}")
        return 1
    n = QuarantineList(path).clear()
    print(f"cleared {n} entries from {path}")
    return 0


def register_runs(sub: argparse._SubParsersAction) -> None:
    rn = sub.add_parser(
        "runs",
        help="browse the tracking store (the mlflow-ui equivalent for a "
        "plain-FS root): list runs, show one run's params/metrics",
    )
    rsub = rn.add_subparsers(dest="runs_cmd", required=True)
    # Same flag name, default, and env override as every writing command
    # (_add_tracking_args), so the browser reads where the writers wrote.
    root = os.environ.get("DSST_TRACKING_ROOT", DEFAULT_TRACKING_ROOT)
    root_help = (
        f"run-store root (default ./{DEFAULT_TRACKING_ROOT}, or env "
        "DSST_TRACKING_ROOT)"
    )

    ls = rsub.add_parser("list", help="one JSON line per run, newest first")
    ls.add_argument("--tracking-root", default=root, help=root_help)
    ls.add_argument("--experiment", default=None)
    ls.set_defaults(fn=_cmd_runs_list)

    sh = rsub.add_parser(
        "show", help="full record of one run (meta, params, last metrics)"
    )
    sh.add_argument("run", help="EXPERIMENT/RUN_ID (as `runs list` prints)")
    sh.add_argument("--tracking-root", default=root, help=root_help)
    sh.set_defaults(fn=_cmd_runs_show)

    dr = rsub.add_parser(
        "doctor",
        help="crash-only store sweep: classify every run from its "
        "journal (PID + boot id), durably mark dead RUNNING runs "
        "INTERRUPTED, clean stranded .tmp files, and report resumable "
        "checkpoints; --resume relaunches each interrupted run's "
        "recorded command with --resume-auto",
    )
    dr.add_argument("--tracking-root", default=root, help=root_help)
    dr.add_argument("--experiment", default=None)
    dr.add_argument(
        "--json", action="store_true",
        help="emit the full classification report as one JSON document",
    )
    dr.add_argument(
        "--resume", action="store_true",
        help="after the sweep, re-execute the recorded dsst command of "
        "each interrupted run that has a resumable checkpoint (or a "
        "journaled HPO trial log), with --resume-auto ensured — "
        "sequentially, newest run per checkpoint dir first; what a "
        "supervisor runs so a recovered TPU VM re-enters training "
        "instead of idling",
    )
    dr.set_defaults(fn=_cmd_runs_doctor)


def _cmd_runs_list(args: argparse.Namespace) -> int:
    from ..tracking import list_runs

    runs = list_runs(args.tracking_root, args.experiment)
    for meta in runs:
        print(json.dumps(meta))
    if not runs:
        print(f"no runs under {args.tracking_root}"
              + (f" (experiment {args.experiment})" if args.experiment
                 else ""),
              file=sys.stderr)
    return 0


def _cmd_runs_show(args: argparse.Namespace) -> int:
    from ..tracking import load_run

    if "/" not in args.run:
        print(f"expected EXPERIMENT/RUN_ID, got {args.run!r}")
        return 1
    experiment, run_id = args.run.split("/", 1)
    try:
        print(json.dumps(
            load_run(args.tracking_root, experiment, run_id), indent=1
        ))
    except (OSError, json.JSONDecodeError, KeyError):
        # Missing run, stray file in the path, a truncated meta.json
        # from a killed writer, or a metrics line missing name/value/step
        # (foreign writer) — same friendly diagnosis either way.
        print(f"no readable run {args.run} under {args.tracking_root}")
        return 1
    return 0


def _cmd_runs_doctor(args: argparse.Namespace) -> int:
    from ..tracking import sweep_interrupted

    if not Path(args.tracking_root).is_dir():
        print(f"no run store at {args.tracking_root}")
        return 0
    report = sweep_interrupted(args.tracking_root, args.experiment)
    if args.json:
        print(json.dumps({"root": str(args.tracking_root),
                          "runs": report}))
    else:
        for cls in report:
            line = (
                f"{cls['experiment']}/{cls['run_id']}: "
                f"{cls['effective_status']}"
            )
            if cls.get("marked"):
                line += f" (was RUNNING, pid {cls['pid']} dead; marked)"
            if cls.get("resumable_step") is not None:
                line += (
                    f" — resumable: step {cls['resumable_step']} in "
                    f"{cls['checkpoint_dir']}"
                )
            if (
                cls["effective_status"] == "INTERRUPTED"
                and cls.get("trace_file")
                and Path(cls["trace_file"]).exists()
            ):
                line += (
                    f" — flight recorder: {cls['trace_file']} "
                    "(dsst trace tail)"
                )
            if (
                cls["effective_status"] == "INTERRUPTED"
                and cls.get("firing_alerts")
            ):
                line += (
                    " — SLO alerts firing at death: "
                    + ", ".join(cls["firing_alerts"])
                )
            print(line)
        n_marked = sum(1 for c in report if c.get("marked"))
        print(
            f"{len(report)} run(s), {n_marked} newly marked INTERRUPTED, "
            f"{sum(1 for c in report if c.get('resumable_step') is not None)}"
            " resumable"
        )
    if not args.resume:
        return 0
    return _doctor_resume(report)


def _doctor_resume(report: list[dict]) -> int:
    """Re-execute interrupted runs' recorded commands with --resume-auto.

    One relaunch per checkpoint dir (the newest run wins — older
    interrupted runs of the same dir are superseded by the resumed one);
    journal-only HPO runs resume once per experiment. Sequential on
    purpose: a chip belongs to one process at a time.
    """
    import subprocess

    resumable = [
        c for c in report
        if c["effective_status"] == "INTERRUPTED" and c.get("cmdline")
        and (c.get("resumable_step") is not None
             or c.get("checkpoint_dir")  # journaled at fit start: a run
             # killed before its first committed step revives as a
             # fresh --resume-auto start instead of idling
             or _journal_has_trials(c["run_dir"]))
    ]
    resumable.sort(key=lambda c: c.get("start_time") or 0.0, reverse=True)
    seen_targets: set[str] = set()
    rc = 0
    for cls in resumable:
        target = cls.get("checkpoint_dir") or f"exp:{cls['experiment']}"
        if target in seen_targets:
            continue
        seen_targets.add(target)
        argv = _resume_argv(cls["cmdline"])
        if argv is None:
            continue
        print(f"doctor --resume: {cls['experiment']}/{cls['run_id']} -> "
              + " ".join(argv))
        # DSST_FAULT_PLAN must not leak into revived runs: cli.main
        # exports it on every armed invocation, so a doctor running in
        # a post-chaos environment would otherwise re-arm the very
        # faults (including kN self-kills) that interrupted the run.
        env = {k: v for k, v in os.environ.items()
               if k != "DSST_FAULT_PLAN"}
        # Relative --data/--checkpoint-dir/--tracking-root in the
        # recorded argv only mean what they meant from the dying
        # process's working directory — the journal records it, so the
        # revival runs there, not wherever the doctor happens to be.
        cwd = cls.get("cwd")
        if cwd and not os.path.isdir(cwd):
            print(f"doctor --resume: recorded cwd {cwd} is gone; "
                  "skipping " + cls["run_id"])
            rc = rc or 1
            continue
        proc = subprocess.run(
            [sys.executable, "-m", "dss_ml_at_scale_tpu.config.cli",
             *argv],
            env=env,
            cwd=cwd,
        )
        rc = rc or proc.returncode
    if not resumable:
        print("doctor --resume: nothing resumable")
    return rc


def _journal_has_trials(run_dir: str) -> bool:
    from ..tracking import read_journal

    return any(e.get("event") == "trial" for e in read_journal(run_dir))


def _resume_argv(cmdline: list[str]) -> list[str] | None:
    """Recorded dsst argv → relaunch argv: --resume-auto ensured for the
    resumable subcommands, --fault-plan stripped (a chaos-armed run must
    not re-arm its own faults on doctor revival)."""
    argv: list[str] = []
    skip_next = False
    for tok in cmdline:
        if skip_next:
            skip_next = False
            continue
        if tok == "--fault-plan":
            skip_next = True
            continue
        if tok.startswith("--fault-plan="):
            continue
        argv.append(tok)
    subcommands = {"train", "lm", "hpo"}
    if not any(tok in subcommands for tok in argv):
        return None
    if "--resume-auto" not in argv:
        argv.append("--resume-auto")
    return argv


def register_chaos(sub: argparse._SubParsersAction) -> None:
    ch = sub.add_parser(
        "chaos",
        help="SIGKILL chaos soak: run dsst train/hpo/serve as "
        "subprocesses, hard-kill them on a seeded schedule (including "
        "inside the checkpoint-save window via kN fs.* fault entries), "
        "restart with --resume-auto, and assert the crash-only "
        "invariants (bitwise final-params parity with an uninterrupted "
        "run, clean manifest walk, zero stranded .tmp files, every run "
        "terminal)",
    )
    ch.add_argument("--workdir", required=True,
                    help="scratch directory for data/checkpoints/runs/logs")
    ch.add_argument("--workload", choices=["train", "hpo", "serve"],
                    default="train")
    ch.add_argument("--cycles", type=int, default=5,
                    help="SIGKILL cycles before the final uninterrupted run")
    ch.add_argument("--seed", type=int, default=0)
    ch.add_argument("--kill-min", type=float, default=1.0,
                    help="delay-mode kill window lower bound (seconds)")
    ch.add_argument("--kill-max", type=float, default=6.0)
    ch.add_argument("--epochs", type=int, default=3)
    ch.add_argument("--rows", type=int, default=48)
    ch.add_argument("--batch-size", type=int, default=16)
    ch.add_argument("--image-size", type=int, default=32)
    ch.add_argument("--max-evals", type=int, default=8,
                    help="(hpo workload) sweep size")
    ch.add_argument("--checkpoint-dir", default=None,
                    help="(serve workload) trained checkpoint to serve")
    ch.add_argument("--timeout", type=float, default=300.0,
                    help="per-child wall bound (seconds)")
    ch.add_argument("--json", action="store_true",
                    help="emit the full soak report as one JSON document")
    ch.set_defaults(fn=_cmd_chaos)


def _cmd_chaos(args: argparse.Namespace) -> int:
    from ..resilience.chaos import ChaosConfig, run_chaos

    report = run_chaos(ChaosConfig(
        workdir=args.workdir,
        workload=args.workload,
        cycles=args.cycles,
        seed=args.seed,
        kill_min_s=args.kill_min,
        kill_max_s=args.kill_max,
        epochs=args.epochs,
        rows=args.rows,
        batch_size=args.batch_size,
        image_size=args.image_size,
        max_evals=args.max_evals,
        checkpoint_dir=args.checkpoint_dir,
        timeout_s=args.timeout,
    ))
    if args.json:
        print(json.dumps(report))
    else:
        for c in report.get("cycles", []):
            print(f"cycle {c.get('cycle')}: mode={c.get('mode')} "
                  f"rc={c.get('returncode')} wall={c.get('wall_s')}s")
        for name, res in report["invariants"].items():
            print(f"invariant {name}: {'OK' if res.get('ok') else 'FAIL'}"
                  + ("" if res.get("ok") else f" {json.dumps(res)}"))
        print(f"chaos soak: {'OK' if report['ok'] else 'FAILED'}")
    return 0 if report["ok"] else 1


def register_telemetry(sub: argparse._SubParsersAction) -> None:
    tl = sub.add_parser(
        "telemetry",
        help="inspect a run's archived telemetry snapshot and export "
        "span logs as Chrome/Perfetto traces",
    )
    tl.add_argument(
        "--run", default=None, metavar="DIR",
        help="run directory (<root>/<experiment>/<run_id>, as `runs "
        "list` points at) whose telemetry.json to print",
    )
    tl.add_argument(
        "--json", action="store_true",
        help="print the raw snapshot JSON instead of a table",
    )
    tl.add_argument(
        "--export-perfetto", default=None, metavar="OUT",
        help="write a Chrome trace_event JSON (loads in ui.perfetto.dev) "
        "converted from a span JSONL (--spans, or the --run's archived "
        "artifacts/spans.jsonl)",
    )
    tl.add_argument(
        "--spans", default=None, metavar="JSONL",
        help="span JSONL to convert (default: <--run>/artifacts/spans.jsonl)",
    )
    tl.set_defaults(fn=_cmd_telemetry)


def _cmd_telemetry(args: argparse.Namespace) -> int:
    did_something = False
    rc = 0
    # Snapshot first: a missing/empty span archive must not swallow a
    # perfectly readable telemetry.json.
    if args.run:
        snap_file = Path(args.run) / "telemetry.json"
        if not snap_file.exists():
            print(f"no telemetry.json under {args.run} (was the run "
                  "finished by a telemetry-aware dsst?)")
            rc = 1
        else:
            snapshot = json.loads(snap_file.read_text())
            if args.json:
                print(json.dumps(snapshot, indent=1))
            else:
                _print_snapshot_table(snapshot)
            did_something = True
    if args.export_perfetto:
        from ..telemetry import export_perfetto

        spans = args.spans or (
            str(Path(args.run) / "artifacts" / "spans.jsonl")
            if args.run else None
        )
        if spans is None:
            print("--export-perfetto needs --spans (or --run with an "
                  "archived spans.jsonl)")
            return 2
        if not Path(spans).exists():
            print(f"no span log at {spans}")
            return 1
        n = export_perfetto(spans, args.export_perfetto)
        print(f"perfetto trace: {n} events -> {args.export_perfetto}")
        did_something = True
    if not did_something and rc == 0:
        print("nothing to do: pass --run and/or --export-perfetto")
        return 2
    return rc


def _print_snapshot_table(snapshot: dict) -> None:
    rows = []
    for m in snapshot.get("metrics", []):
        labels = m.get("labels") or {}
        name = m["name"] + (
            "{" + ",".join(f'{k}={v}' for k, v in labels.items()) + "}"
            if labels else ""
        )
        if m.get("type") == "histogram":
            count = m.get("count", 0)
            mean = (m.get("sum", 0.0) / count) if count else 0.0
            value = (f"count={count} sum={m.get('sum', 0.0):.6g} "
                     f"mean={mean:.6g}")
        elif m.get("type") == "window":
            qs = " ".join(
                f"p{float(q) * 100:g}="
                + (f"{v:.6g}" if v is not None else "-")
                for q, v in sorted(m.get("quantiles", {}).items())
            )
            value = (f"count={m.get('count', 0)} {qs} "
                     f"[{m.get('window_s', 0):g}s window]")
        else:
            value = f"{m.get('value', 0.0):.6g}"
        rows.append((name, m.get("type", "?"), value))
    if not rows:
        print("(empty snapshot)")
        return
    width = max(len(r[0]) for r in rows)
    print(f"{'METRIC':<{width}}  {'TYPE':<9}  VALUE")
    for name, kind, value in rows:
        print(f"{name:<{width}}  {kind:<9}  {value}")


def register_lint(sub: argparse._SubParsersAction) -> None:
    ln = sub.add_parser(
        "lint",
        help="run the JAX-aware static-analysis suite (trace-safety, "
        "retrace hazards, host-sync-in-hotpath, lock discipline, "
        "registries) over the package",
    )
    ln.add_argument(
        "--rules", default=None, metavar="R1,R2",
        help="comma-separated subset of rules to run (default: all; "
        "see --list-rules)",
    )
    ln.add_argument(
        "--json", action="store_true",
        help="machine-readable output (schema documented in README "
        "'Static analysis'; stable across versions via its 'version' "
        "field) instead of text",
    )
    ln.add_argument(
        "--baseline", default=None, metavar="FILE",
        help="baseline file of accepted pre-existing findings "
        "(default: LINT_BASELINE.json at the repo root)",
    )
    ln.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline to the current findings: existing "
        "entries keep their authored reason, new ones take --reason, "
        "stale ones are dropped",
    )
    ln.add_argument(
        "--reason", default=None, metavar="TEXT",
        help="justification recorded for entries newly added by "
        "--update-baseline (mandatory when any exist)",
    )
    ln.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalog and exit",
    )
    ln.add_argument(
        "--changed", nargs="?", const="HEAD", default=None, metavar="REF",
        help="lint only files changed vs the given git ref (default "
        "HEAD: staged+unstaged+untracked) — the fast pre-commit mode. "
        "Whole-package registry rules (telemetry-registry, fault-sites) "
        "are skipped: they reconcile call sites against a registry "
        "across ALL files and would misfire on a subset",
    )
    ln.set_defaults(fn=_cmd_lint)


def _cmd_lint(args: argparse.Namespace) -> int:
    from ..analysis import (
        DEFAULT_BASELINE,
        LintUsageError,
        checker_catalog,
        load_baseline,
        run_lint,
        write_baseline,
    )

    try:
        if args.list_rules:
            for name, desc in checker_catalog():
                print(f"{name:20s} {desc}")
            return 0
        rules = (
            [r.strip() for r in args.rules.split(",") if r.strip()]
            if args.rules else None
        )
        baseline = (
            Path(args.baseline) if args.baseline else DEFAULT_BASELINE
        )
        paths = None
        if args.changed is not None:
            if args.update_baseline:
                raise LintUsageError(
                    "--changed cannot --update-baseline: a partial scan "
                    "must never rewrite the whole-package baseline"
                )
            paths = _changed_python_files(args.changed)
            if not paths and not args.json:
                # --json keeps its machine contract even on an empty
                # change set: fall through to an empty-scope run so
                # stdout is still one parseable document.
                print(f"dsst lint --changed {args.changed}: no changed "
                      "Python files in scope; nothing to lint")
                return 0
        res = run_lint(rules, baseline_path=baseline, paths=paths)
        if args.update_baseline:
            # Everything currently reported (active + already-baselined)
            # becomes the new baseline; stale keys simply don't survive
            # the rewrite. Entries of rules OUTSIDE this run's selection
            # are preserved verbatim — a --rules subset update must not
            # wipe what it never re-checked.
            old = load_baseline(baseline)
            selected = set(res.rules) | {"suppression"}
            preserved = {
                k: e for k, e in old.items()
                if e.get("rule") not in selected
            }
            added = write_baseline(
                baseline, res.findings + res.baselined, old, args.reason,
                preserved=preserved,
            )
            print(
                f"baseline {baseline}: {len(res.findings)} added "
                f"({added} with new reason), {len(res.baselined)} kept, "
                f"{len(preserved)} preserved (other rules), "
                f"{len(res.stale_baseline)} stale dropped"
            )
            return 0
        print(res.render_json() if args.json else res.render_text())
        # Exit codes are part of the CI contract: 0 clean, 1 findings
        # (or stale baseline ballast), 2 usage error.
        return res.exit_code
    except LintUsageError as e:
        print(f"dsst lint: {e}", file=sys.stderr)
        return 2


def _changed_python_files(ref: str) -> list:
    """Package/scripts ``.py`` files changed vs ``ref`` (plus untracked
    ones) — the ``dsst lint --changed`` scope. Deleted files drop out
    naturally (they no longer exist to lint)."""
    import subprocess

    from ..analysis.core import REPO_ROOT, default_roots

    def git(*argv: str) -> list[str]:
        out = subprocess.run(
            ["git", *argv], cwd=REPO_ROOT, capture_output=True, text=True,
        )
        if out.returncode != 0:
            from ..analysis import LintUsageError

            raise LintUsageError(
                f"git {' '.join(argv)} failed: {out.stderr.strip()}"
            )
        return [line for line in out.stdout.splitlines() if line.strip()]

    names = set(git("diff", "--name-only", ref))
    names.update(git("ls-files", "--others", "--exclude-standard"))
    # Scope to the lint scan roots so --changed and the full scan agree
    # on what is lintable — derived, not hardcoded, so a new scan root
    # is picked up here automatically.
    prefixes = []
    for _, root in default_roots():
        try:
            rel = Path(root).resolve().relative_to(REPO_ROOT).as_posix()
        except ValueError:
            continue
        prefixes.append(rel + "/")
    out = []
    for name in sorted(names):
        p = REPO_ROOT / name
        if p.suffix == ".py" and p.exists() and name.startswith(
            tuple(prefixes)
        ):
            out.append(p)
    return out


def register_audit(sub: argparse._SubParsersAction) -> None:
    au = sub.add_parser(
        "audit",
        help="IR-level program audit: trace the registry of real "
        "compiled entrypoints on an abstract 8-device mesh and check "
        "donation, dtypes, collectives, host callbacks, and the "
        "compiled-program baseline (AUDIT_BASELINE.json)",
    )
    au.add_argument(
        "--entrypoints", default=None, metavar="E1,E2",
        help="comma-separated subset of registry entrypoints "
        "(default: all; see --list-entrypoints)",
    )
    au.add_argument(
        "--rules", default=None, metavar="R1,R2",
        help="comma-separated subset of audit rules (default: all; "
        "see --list-rules)",
    )
    au.add_argument(
        "--json", action="store_true",
        help="machine-readable output (schema documented in README "
        "'Program audit') instead of text",
    )
    au.add_argument(
        "--baseline", default=None, metavar="FILE",
        help="program/finding baseline (default: AUDIT_BASELINE.json "
        "at the repo root)",
    )
    au.add_argument(
        "--update-baseline", action="store_true",
        help="re-pin every entrypoint's program hash and cost budgets "
        "to the current build and rewrite accepted findings (existing "
        "entries keep their authored reason, new ones take --reason)",
    )
    au.add_argument(
        "--reason", default=None, metavar="TEXT",
        help="justification recorded for entries newly added by "
        "--update-baseline (mandatory when any exist)",
    )
    au.add_argument(
        "--list-rules", action="store_true",
        help="print the audit rule catalog and exit",
    )
    au.add_argument(
        "--list-entrypoints", action="store_true",
        help="print the entrypoint registry and exit",
    )
    au.set_defaults(fn=_cmd_audit)


def _cmd_audit(args: argparse.Namespace) -> int:
    # The abstract mesh needs >=8 devices; on a CPU host that means
    # multiplexing the host platform BEFORE backend init. Setting the
    # flag is safe even if another backend wins (TPU hosts have >=8
    # real devices; default_audit_mesh validates either way).
    import os

    flag = "--xla_force_host_platform_device_count=8"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag
        ).strip()

    from ..analysis.audit import (
        DEFAULT_AUDIT_BASELINE,
        AuditUsageError,
        entrypoint_names,
        load_audit_baseline,
        rule_catalog,
        run_audit,
        write_audit_baseline,
    )

    try:
        if args.list_rules:
            for name, desc in rule_catalog():
                print(f"{name:22s} {desc}")
            return 0
        if args.list_entrypoints:
            for name in entrypoint_names():
                print(name)
            return 0
        entrypoints = (
            [e.strip() for e in args.entrypoints.split(",") if e.strip()]
            if args.entrypoints else None
        )
        rules = (
            [r.strip() for r in args.rules.split(",") if r.strip()]
            if args.rules else None
        )
        baseline = (
            Path(args.baseline) if args.baseline
            else DEFAULT_AUDIT_BASELINE
        )
        if args.update_baseline and (entrypoints or rules):
            # Same contract as `lint --changed`: the baseline is a
            # whole-registry truth. write_audit_baseline rebuilds
            # 'programs' from this run alone, so a subset update would
            # silently drop every pin (and, under --rules without
            # program-baseline, every cost budget) it didn't re-check.
            raise AuditUsageError(
                "--update-baseline needs the full audit: an "
                "--entrypoints/--rules subset must never rewrite the "
                "whole-registry baseline"
            )
        res = run_audit(entrypoints, rules=rules, baseline_path=baseline)
        if args.update_baseline:
            old = load_audit_baseline(baseline)
            added = write_audit_baseline(baseline, res, old, args.reason)
            print(
                f"audit baseline {baseline}: {len(res.programs)} "
                f"program(s) pinned, {added} finding(s) newly accepted, "
                f"{len(res.stale_baseline)} stale dropped"
            )
            return 0
        print(res.render_json() if args.json else res.render_text())
        return res.exit_code
    except AuditUsageError as e:
        print(f"dsst audit: {e}", file=sys.stderr)
        return 2


def register_sanitize(sub: argparse._SubParsersAction) -> None:
    sz = sub.add_parser(
        "sanitize",
        help="runtime thread sanitizer (third analysis tier): run named "
        "workloads with lock/thread instrumentation armed and report "
        "lock-order cycles (potential deadlocks, with both acquisition "
        "stacks), guarded-by violations, unjoined threads, and leaked "
        "locks against SANITIZE_BASELINE.json",
    )
    sz.add_argument(
        "--workloads", default=None, metavar="W1,W2",
        help="comma-separated subset of workloads to run (default: all; "
        "see --list-workloads). Subset runs skip stale-baseline "
        "enforcement — they cannot prove an unexercised finding gone",
    )
    sz.add_argument(
        "--json", action="store_true",
        help="machine-readable output (schema documented in README "
        "'Runtime sanitizer') instead of text",
    )
    sz.add_argument(
        "--baseline", default=None, metavar="FILE",
        help="baseline of accepted pre-existing findings (default: "
        "SANITIZE_BASELINE.json at the repo root)",
    )
    sz.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline to the current findings: existing "
        "entries keep their authored reason, new ones take --reason, "
        "stale ones are dropped (full workload set only)",
    )
    sz.add_argument(
        "--reason", default=None, metavar="TEXT",
        help="justification recorded for entries newly added by "
        "--update-baseline (mandatory when any exist)",
    )
    sz.add_argument(
        "--list-workloads", action="store_true",
        help="print the workload catalog and exit",
    )
    sz.add_argument(
        "--list-rules", action="store_true",
        help="print the sanitizer rule catalog and exit",
    )
    sz.set_defaults(fn=_cmd_sanitize)


def _cmd_sanitize(args: argparse.Namespace) -> int:
    from ..analysis.sanitize import (
        DEFAULT_SANITIZE_BASELINE,
        RULES,
        SanitizeUsageError,
        build_result,
        run_workloads,
        sanitize_scope,
        workload_catalog,
        workload_names,
    )
    from ..analysis.sanitize.report import update_baseline

    try:
        if args.list_workloads:
            for name, desc in workload_catalog():
                print(f"{name:12s} {desc}")
            return 0
        if args.list_rules:
            for name, desc in sorted(RULES.items()):
                print(f"{name:16s} {desc}")
            return 0
        names = (
            [w.strip() for w in args.workloads.split(",") if w.strip()]
            if args.workloads else workload_names()
        )
        unknown = sorted(set(names) - set(workload_names()))
        if unknown:
            raise SanitizeUsageError(
                f"unknown workload(s) {', '.join(unknown)}; known: "
                f"{', '.join(workload_names())}"
            )
        full_run = set(names) == set(workload_names())
        if args.update_baseline and not full_run:
            # The baseline is a whole-suite truth (the lint --changed /
            # audit-subset discipline): a subset run would drop every
            # entry its workloads never exercised.
            raise SanitizeUsageError(
                "--update-baseline needs the full workload set: a "
                "subset run must never rewrite the whole baseline"
            )
        baseline = (
            Path(args.baseline) if args.baseline
            else DEFAULT_SANITIZE_BASELINE
        )
        with sanitize_scope() as scope:
            run_workloads(names)
        res = build_result(
            scope, names, baseline_path=baseline, full_run=full_run,
        )
        if args.update_baseline:
            added = update_baseline(baseline, res, args.reason)
            print(
                f"sanitize baseline {baseline}: "
                f"{len(res.findings)} added ({added} with new reason), "
                f"{len(res.baselined)} kept, "
                f"{len(res.stale_baseline)} stale dropped"
            )
            return 0
        print(res.render_json() if args.json else res.render_text())
        return res.exit_code
    except SanitizeUsageError as e:
        print(f"dsst sanitize: {e}", file=sys.stderr)
        return 2


def register_trace(sub: argparse._SubParsersAction) -> None:
    tr = sub.add_parser(
        "trace",
        help="causal tracing tools over a run's flight-recorder tail "
        "(or any span JSONL): tail reconstructs a dead run's last "
        "events including spans still open at the kill, export writes "
        "a Perfetto trace with cross-thread flow arrows per trace id, "
        "attribution breaks each training step into "
        "data-wait/transfer/compute/host and flags step-time anomalies "
        "with their causal children",
    )
    tsub = tr.add_subparsers(dest="trace_cmd", required=True)

    def _add_source(p):
        p.add_argument(
            "--run", default=None, metavar="DIR",
            help="run directory (<root>/<experiment>/<run_id>): reads "
            "the flight-recorder tail its journal registered "
            "(flightrec.jsonl)",
        )
        p.add_argument(
            "--file", default=None, metavar="JSONL",
            help="explicit flight-recorder tail or span JSONL "
            "(overrides --run)",
        )

    tl = tsub.add_parser(
        "tail",
        help="the last events of a (possibly SIGKILLed) run; "
        "begin-only spans are flagged OPEN — the in-flight work at "
        "the kill",
    )
    _add_source(tl)
    tl.add_argument("-n", "--events", type=int, default=32,
                    help="how many trailing events to show")
    tl.add_argument("--json", action="store_true",
                    help="one JSON object per line instead of the table")
    tl.set_defaults(fn=_cmd_trace_tail)

    ex = tsub.add_parser(
        "export",
        help="Perfetto trace_event JSON: labeled process/thread lanes "
        "(ph M) and flow arrows (ph s/f) stitching each trace id "
        "across threads; loads in ui.perfetto.dev",
    )
    _add_source(ex)
    ex.add_argument(
        "--merge", nargs="+", default=None, metavar="JSONL",
        help="merge N replicas' recorder files into ONE timeline: each "
        "file gets its own pid band + process lane, and propagated "
        "trace ids draw flow arrows ACROSS files (overrides "
        "--run/--file)",
    )
    ex.add_argument("--out", required=True, metavar="OUT",
                    help="output trace file")
    ex.set_defaults(fn=_cmd_trace_export)

    at = tsub.add_parser(
        "attribution",
        help="per-step breakdown (data-wait / transfer / compute / "
        "host) from the step traces, plus z-score step-time anomalies "
        "with the anomalous step's causal children",
    )
    _add_source(at)
    at.add_argument("--zscore", type=float, default=3.0,
                    help="|z| threshold flagging a step-time anomaly")
    at.add_argument("--json", action="store_true",
                    help="emit the full report as one JSON document")
    at.set_defaults(fn=_cmd_trace_attribution)


def _trace_source(args: argparse.Namespace) -> Path | None:
    """Resolve tail|export|attribution's input file; None + message on
    failure (callers exit 2)."""
    if args.file:
        p = Path(args.file)
        if not p.exists():
            print(f"no trace file at {p}")
            return None
        return p
    if args.run:
        from ..tracking import classify_run

        cls = classify_run(args.run)
        candidates = [
            Path(cls["trace_file"]) if cls.get("trace_file") else None,
            Path(args.run) / "flightrec.jsonl",
        ]
        for p in candidates:
            if p is not None and p.exists():
                return p
        print(f"no flight-recorder tail under {args.run} (was the run "
              "started by a trace-aware dsst?)")
        return None
    print("pass --run DIR or --file JSONL")
    return None


def _cmd_trace_tail(args: argparse.Namespace) -> int:
    from ..telemetry import flightrec

    path = _trace_source(args)
    if path is None:
        return 2
    events = flightrec.read_events(path)
    if not events:
        print(f"no parseable events in {path}")
        return 1
    complete, opens = flightrec.reconstruct(events)
    # Trailing window: the last N closed spans, then EVERY open span —
    # the open ones are the point (in-flight work at the kill). The
    # window can be zero (opens alone fill -n); list[-0:] would be the
    # WHOLE list, so slice from an explicit start index.
    n_closed = max(args.events - len(opens), 0)
    rows = complete[len(complete) - min(n_closed, len(complete)):] \
        if n_closed else []
    rows = rows + [{**o, "open": True} for o in opens]
    if args.json:
        for r in rows:
            print(json.dumps(r))
        return 0
    print(f"{path}: {len(complete)} closed span(s), {len(opens)} open")
    for r in rows:
        ts = time.strftime("%H:%M:%S", time.localtime(r.get("ts", 0.0)))
        dur = "OPEN" if r.get("open") else f"{r.get('dur', 0.0)*1e3:9.2f}ms"
        trace = r.get("trace", "-")
        kindtag = f"[{r['kind']}]" if r.get("kind") else ""
        argstr = ""
        if r.get("args"):
            argstr = " " + ",".join(
                f"{k}={v}" for k, v in r["args"].items() if k != "open"
            )
        print(f"{ts} {r.get('thread', '?'):<22} {r.get('name', '?'):<20} "
              f"{dur:>12} trace={trace} {kindtag}{argstr}")
    if opens:
        print(f"{len(opens)} span(s) were OPEN when recording stopped "
              "(in-flight at the kill)")
    return 0


def _cmd_trace_export(args: argparse.Namespace) -> int:
    from ..telemetry.spans import (
        load_span_jsonl,
        merge_replica_spans,
        to_perfetto,
    )

    process_names = None
    if getattr(args, "merge", None):
        missing = [p for p in args.merge if not Path(p).exists()]
        if missing:
            print(f"no trace file at {missing[0]}")
            return 2
        events, process_names = merge_replica_spans(args.merge)
        src = f"{len(args.merge)} file(s)"
    else:
        path = _trace_source(args)
        if path is None:
            return 2
        events = load_span_jsonl(path)
        src = str(path)
    if not events:
        print(f"no parseable events in {src}")
        return 1
    # Build in memory, count from the dict, write once — re-reading the
    # file just written (possibly tens of MB) to count flows is waste.
    trace = to_perfetto(events, process_names=process_names)
    flows = sum(
        1 for e in trace["traceEvents"] if e.get("ph") in ("s", "f")
    )
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(trace, f)
    print(f"perfetto trace: {len(events)} span(s), {flows} flow "
          f"event(s) -> {args.out}")
    return 0


def _cmd_trace_attribution(args: argparse.Namespace) -> int:
    from ..telemetry import flightrec

    # Attribution buckets: span name -> where a step's wall time went.
    # Sourced from telemetry.catalog.SPAN_ATTRIBUTION — the ONE mapping
    # this command and the bench harness's e2e cross-check share (names
    # held to KNOWN_SPANS by the span-discipline lint), so the two
    # consumers cannot drift apart. Imported at command time: loading
    # the telemetry package pulls jax, which every other subcommand's
    # startup must not pay.
    from ..telemetry.catalog import SPAN_ATTRIBUTION as _ATTRIBUTION
    from ..telemetry.catalog import SPAN_NESTED

    path = _trace_source(args)
    if path is None:
        return 2
    complete, opens = flightrec.reconstruct(flightrec.read_events(path))
    by_trace: dict[str, list[dict]] = {}
    for e in complete:
        if (e.get("kind") == "step" and e.get("trace")
                and e.get("name") not in SPAN_NESTED):
            by_trace.setdefault(e["trace"], []).append(e)
    steps = []
    for trace_id, spans in by_trace.items():
        compute = [s for s in spans if s["name"] == "train_step"]
        if not compute:
            continue  # eval/warmup batches: staged but never stepped
        buckets = {"data_wait": 0.0, "transfer": 0.0, "compute": 0.0,
                   "host": 0.0}
        for s in spans:
            buckets[_ATTRIBUTION.get(s["name"], "host")] += s.get(
                "dur", 0.0
            )
        steps.append({
            "step": (compute[0].get("args") or {}).get("step"),
            "trace": trace_id,
            "ts": compute[0].get("ts", 0.0),
            **{k: round(v * 1e3, 3) for k, v in buckets.items()},
            "total": round(sum(buckets.values()) * 1e3, 3),
            "spans": [
                {"name": s["name"], "thread": s.get("thread"),
                 "dur_ms": round(s.get("dur", 0.0) * 1e3, 3)}
                for s in sorted(spans, key=lambda s: s.get("ts", 0.0))
            ],
        })
    if not steps:
        print(f"no step traces in {path} (is this a training run's "
              "flight recorder?)")
        return 1
    steps.sort(key=lambda s: s["ts"])
    # Anomalies are flagged on TOTAL traced step time: a data-wait or
    # transfer spike IS a step-time anomaly (the feeder-stall case this
    # tool exists to surface) even when compute stays nominal.
    durs = [s["total"] for s in steps]
    mean = sum(durs) / len(durs)
    var = sum((d - mean) ** 2 for d in durs) / len(durs)
    std = var ** 0.5
    anomalies = []
    for s in steps:
        z = (s["total"] - mean) / std if std > 0 else 0.0
        s["z"] = round(z, 2)
        if abs(z) >= args.zscore:
            anomalies.append(s)
    report = {
        "file": str(path),
        "steps": len(steps),
        "total_ms_mean": round(mean, 3),
        "total_ms_std": round(std, 3),
        "compute_ms_mean": round(
            sum(s["compute"] for s in steps) / len(steps), 3
        ),
        "data_wait_ms_mean": round(
            sum(s["data_wait"] for s in steps) / len(steps), 3
        ),
        "transfer_ms_mean": round(
            sum(s["transfer"] for s in steps) / len(steps), 3
        ),
        "host_ms_mean": round(
            sum(s["host"] for s in steps) / len(steps), 3
        ),
        "zscore_threshold": args.zscore,
        "anomalies": anomalies,
        "open_spans": [o.get("name") for o in opens],
    }
    if args.json:
        report["per_step"] = [
            {k: v for k, v in s.items() if k != "spans"} for s in steps
        ]
        print(json.dumps(report))
        return 0
    print(f"{len(steps)} step(s): total {mean:.3f}ms ± {std:.3f}ms, "
          f"compute {report['compute_ms_mean']}ms, "
          f"data-wait {report['data_wait_ms_mean']}ms, "
          f"transfer {report['transfer_ms_mean']}ms, "
          f"host {report['host_ms_mean']}ms (means per step)")
    hdr = (f"{'STEP':>6} {'DATA':>9} {'XFER':>9} {'COMPUTE':>9} "
           f"{'HOST':>9} {'TOTAL':>9} {'Z':>6}")
    print(hdr)
    for s in steps:
        print(f"{str(s['step']):>6} {s['data_wait']:>9.3f} "
              f"{s['transfer']:>9.3f} {s['compute']:>9.3f} "
              f"{s['host']:>9.3f} {s['total']:>9.3f} {s['z']:>6.2f}")
    for a in anomalies:
        print(f"anomaly: step {a['step']} (z={a['z']}) — causal children:")
        for s in a["spans"]:
            print(f"    {s['name']:<20} {s['dur_ms']:>9.3f}ms "
                  f"on {s['thread']}")
    if not anomalies:
        print(f"no |z| >= {args.zscore:g} step-time anomalies")
    return 0


def register_bench(sub: argparse._SubParsersAction) -> None:
    bn = sub.add_parser(
        "bench",
        help="performance regression harness (fourth analysis tier): "
        "run registered scenarios in isolated children with "
        "noise-aware repetitions and judge them against the "
        "environment-fingerprinted BENCH_BASELINE.json; `dsst bench "
        "profile <scenario>` merges flight-recorder spans with a "
        "jax.profiler trace into one Perfetto timeline",
    )
    bn.add_argument(
        "--scenarios", default=None, metavar="S1,S2",
        help="comma-separated subset of scenarios (default: every "
        "non-tpu scenario; see --list-scenarios)",
    )
    bn.add_argument(
        "--tier", default=None, metavar="TIER",
        help="run one tier (tier1 | slow | tpu) instead of naming "
        "scenarios — tier1 is the CI smoke subset",
    )
    bn.add_argument(
        "--repetitions", type=int, default=None, metavar="N",
        help="override every selected scenario's repetition count",
    )
    bn.add_argument(
        "--in-process", action="store_true",
        help="measure inline instead of per-scenario child processes "
        "(debugging; loses crash isolation)",
    )
    bn.add_argument(
        "--json", action="store_true",
        help="machine-readable output (schema documented in README "
        "'Benchmarking') instead of text",
    )
    bn.add_argument(
        "--baseline", default=None, metavar="FILE",
        help="baseline file (default: BENCH_BASELINE.json at the repo "
        "root)",
    )
    bn.add_argument(
        "--update-baseline", action="store_true",
        help="record this run's summaries for the current environment "
        "fingerprint: existing entries keep their authored reason, new "
        "ones take --reason, stale ones are dropped; other "
        "fingerprints' entries are preserved verbatim",
    )
    bn.add_argument(
        "--require-baseline", action="store_true",
        help="strict gating: a gated metric with NO committed entry "
        "under this host's fingerprint is a failing finding instead of "
        "a silent 'no-baseline' pass — for preflights that must never "
        "run ungated on a new host",
    )
    bn.add_argument(
        "--reason", default=None, metavar="TEXT",
        help="justification recorded for entries newly added by "
        "--update-baseline (mandatory when any exist)",
    )
    bn.add_argument(
        "--list-scenarios", action="store_true",
        help="print the scenario registry and exit",
    )
    bsub = bn.add_subparsers(dest="bench_cmd")
    pf = bsub.add_parser(
        "profile",
        help="run one scenario under the flight recorder AND "
        "jax.profiler; merge both into ONE Perfetto file (host "
        "handoffs and device ops on the same timeline, flow arrows "
        "intact)",
    )
    pf.add_argument("scenario", help="scenario to profile")
    pf.add_argument("--out", required=True, metavar="FILE",
                    help="merged Perfetto trace output path")
    # Own dest: a subparser option sharing dest="repetitions" would
    # apply ITS default over a value already parsed by the parent
    # (`dsst bench --repetitions 5 profile ...` silently became 1).
    pf.add_argument("--repetitions", type=int, default=None,
                    dest="profile_repetitions",
                    help="repetitions to trace (default: 1, or the "
                    "parent --repetitions when given before 'profile')")
    pf.add_argument(
        "--min-profiler-dur-us", type=float, default=5.0,
        help="drop jax.profiler complete events shorter than this "
        "(the runtimes emit ~1M sub-microsecond TraceMes per traced "
        "second; dropped count is reported). 0 keeps everything",
    )
    bn.set_defaults(fn=_cmd_bench)


def _cmd_bench(args: argparse.Namespace) -> int:
    # Scenarios that execute audited entrypoints need the same >=8
    # abstract devices `dsst audit` multiplexes — set before backend
    # init (children inherit; profile runs in-process). MESH_FLAG is
    # the ONE definition the parent and the needs_mesh child runner
    # share: disagreeing would silently fork the fingerprint's device
    # count. (bench.core imports no jax at module level, so this stays
    # cheap at command time.)
    import os

    from ..bench.core import MESH_FLAG

    if MESH_FLAG not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + MESH_FLAG
        ).strip()

    from ..bench import (
        DEFAULT_BENCH_BASELINE,
        BenchUsageError,
        load_bench_baseline,
        run_bench,
        scenario_catalog,
        write_bench_baseline,
    )

    in_process = (
        getattr(args, "bench_cmd", None) == "profile" or args.in_process
    )
    if in_process:
        # Only a process that measures inline touches jax; the isolating
        # parent stays off it (its children hold the chip in turn).
        from ..runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
    try:
        if getattr(args, "bench_cmd", None) == "profile":
            from ..bench.profile import profile_scenario

            reps = args.profile_repetitions
            if reps is None:
                reps = args.repetitions if args.repetitions else 1
            report = profile_scenario(
                args.scenario, args.out, repetitions=reps,
                min_profiler_dur_us=args.min_profiler_dur_us,
            )
            print(
                f"merged perfetto trace: {report['spans']} span(s), "
                f"{report['flows']} flow event(s), "
                f"{report['profiler_events']} profiler event(s) "
                f"(+{report['profiler_events_dropped']} dropped under "
                f"{args.min_profiler_dur_us:g}us) -> {report['out']}"
            )
            if report.get("mfu"):
                b = report["mfu"]
                util = b.get("utilization")
                print(
                    f"achieved FLOPs/s ({b['entrypoint']}): "
                    f"{b['achieved_flops_per_sec']:.4g}"
                    + (f" ({util:.2%} of peak)" if util is not None else "")
                )
            return 0
        if args.list_scenarios:
            for name, tier, desc in scenario_catalog():
                print(f"{name:20s} [{tier:5s}] {desc}")
            return 0
        scenarios = (
            [s.strip() for s in args.scenarios.split(",") if s.strip()]
            if args.scenarios else None
        )
        if scenarios and args.tier:
            raise BenchUsageError(
                "--scenarios and --tier are exclusive selections"
            )
        baseline = (
            Path(args.baseline) if args.baseline else DEFAULT_BENCH_BASELINE
        )
        res = run_bench(
            scenarios, tier=args.tier, repetitions=args.repetitions,
            baseline_path=baseline, isolation=not in_process,
            require_baseline=args.require_baseline,
        )
        if args.update_baseline:
            old = load_bench_baseline(baseline)
            added = write_bench_baseline(baseline, res, old, args.reason)
            print(
                f"bench baseline {baseline}: {len(res.results)} "
                f"scenario(s) recorded under {res.fingerprint_key} "
                f"({added} with new reason)"
            )
            return 0
        print(res.render_json() if args.json else res.render_text())
        return res.exit_code
    except BenchUsageError as e:
        print(f"dsst bench: {e}", file=sys.stderr)
        return 2


# --------------------------------------------------------------------------
# slo / top (the live monitoring plane's CLI face)
# --------------------------------------------------------------------------

def _add_slo_source_args(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--url", default="http://127.0.0.1:8008", metavar="URL",
        help="a running dsst serve process (its /slo endpoint is "
        "scraped); default matches `dsst serve`'s default port",
    )
    p.add_argument(
        "--report", default=None, metavar="JSON",
        help="judge a saved /slo status JSON instead of a live process",
    )


def register_slo(sub: argparse._SubParsersAction) -> None:
    so = sub.add_parser(
        "slo",
        help="live SLOs: declared objectives, windowed values, "
        "burn rates, and alert states — baseline-free (the objectives "
        "are code, telemetry.slo.default_objectives)",
    )
    ssub = so.add_subparsers(dest="slo_cmd", required=True)
    st = ssub.add_parser(
        "status", help="one status frame: every objective's live "
        "value, budget remaining, burn rates, and alert state",
    )
    _add_slo_source_args(st)
    _add_fleet_args(st)
    st.add_argument("--json", action="store_true",
                    help="print the raw /slo document (schema v1)")
    st.set_defaults(fn=_cmd_slo_status)
    ck = ssub.add_parser(
        "check", help="gate on the SLO plane: exit 1 if any objective "
        "is firing",
    )
    _add_slo_source_args(ck)
    _add_fleet_args(ck)
    ck.add_argument("--json", action="store_true")
    ck.add_argument(
        "--strict", action="store_true",
        help="also fail on objectives in the pending state",
    )
    ck.set_defaults(fn=_cmd_slo_check)
    wa = ssub.add_parser(
        "watch", help="poll /slo and redraw the status frame",
    )
    _add_slo_source_args(wa)
    _add_fleet_args(wa)
    wa.add_argument("--interval", type=float, default=2.0,
                    metavar="SECONDS")
    wa.add_argument(
        "--iterations", type=int, default=0, metavar="N",
        help="stop after N frames (0 = until Ctrl-C)",
    )
    wa.set_defaults(fn=_cmd_slo_watch)


def _slo_parse_url(url: str) -> tuple[str, int]:
    if "://" in url and not url.startswith("http://"):
        # A clear refusal beats the int() parse error https:// would
        # otherwise surface as.
        raise ValueError(
            f"only http:// URLs are supported, got {url!r}"
        )
    hostport = url.removeprefix("http://").rstrip("/")
    host, _, port_s = hostport.partition(":")
    return host or "127.0.0.1", int(port_s or 8008)


def _slo_http_json(url: str, path: str) -> dict:
    import http.client

    host, port = _slo_parse_url(url)
    conn = http.client.HTTPConnection(host, port, timeout=10)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        raise OSError(f"GET {path} -> HTTP {resp.status}")
    return json.loads(body)


def _slo_fetch_status(args: argparse.Namespace) -> dict | None:
    """The /slo document from --report or --url; None (with a message
    on stderr) when the source is unusable — callers exit 2."""
    if args.report:
        try:
            doc = json.loads(Path(args.report).read_text())
        except (OSError, json.JSONDecodeError) as e:
            print(f"dsst slo: cannot read --report {args.report}: {e}",
                  file=sys.stderr)
            return None
        if not isinstance(doc, dict) or "objectives" not in doc:
            print(
                f"dsst slo: {args.report} carries no SLO status "
                "document (expected a /slo JSON)",
                file=sys.stderr,
            )
            return None
        return doc
    try:
        return _slo_http_json(args.url, "/slo")
    except (OSError, ValueError) as e:
        print(f"dsst slo: cannot scrape {args.url}/slo: {e}",
              file=sys.stderr)
        return None


def _slo_fmt_value(obj: dict) -> str:
    v = obj.get("value")
    if v is None:
        return "-"
    if obj.get("unit") == "s":
        return f"{v * 1000:.1f}ms"
    return f"{v:.4g}"


def _slo_fmt_budget(obj: dict) -> str:
    b = obj.get("budget")
    if b is None:
        return "unarmed"
    if obj.get("unit") == "s":
        return f"{b * 1000:g}ms"
    return f"{b:g}"


def _slo_render_text(doc: dict) -> list[str]:
    rows = [
        (
            o["name"], o["state"], _slo_fmt_value(o), _slo_fmt_budget(o),
            f"{o['burn_fast']:.2f}/{o['burn_slow']:.2f}",
            ("-" if o.get("budget_remaining") is None
             else f"{o['budget_remaining']:.2f}"),
            str(o.get("samples", 0)),
        )
        for o in doc.get("objectives", [])
    ]
    header = ("OBJECTIVE", "STATE", "VALUE", "BUDGET", "BURN f/s",
              "REMAINING", "SAMPLES")
    widths = [
        max(len(header[i]), *(len(r[i]) for r in rows)) if rows
        else len(header[i])
        for i in range(len(header))
    ]
    lines = ["  ".join(h.ljust(w) for h, w in zip(header, widths))]
    for r in rows:
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    firing = doc.get("firing", [])
    lines.append(
        "firing: " + (", ".join(firing) if firing else "(none)")
    )
    return lines


# -- fleet mode (slo --fleet / top --fleet) ---------------------------------


def _add_fleet_args(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--fleet", nargs="+", default=None, metavar="ENDPOINT",
        help="fleet mode: scrape N replicas' /telemetry endpoints "
        "(host:port ...), merge their registries and SLO windows, and "
        "judge the FLEET instead of one process",
    )
    p.add_argument(
        "--fleet-timeout", type=float, default=2.0, metavar="SECONDS",
        help="per-cycle scrape budget; a replica that doesn't answer "
        "inside it costs its column, never the cycle",
    )
    p.add_argument(
        "--fleet-journal", default=None, metavar="JSONL",
        help="journal each fleet scrape cycle crash-durably to this "
        "path (outcome per replica, merged firing set)",
    )


def _fleet_aggregator(args: argparse.Namespace):
    from ..telemetry import federation

    return federation.FleetAggregator(
        args.fleet,
        timeout_s=args.fleet_timeout,
        journal_path=args.fleet_journal,
    )


def _fleet_replica_rows(view) -> list[str]:
    """Per-replica columns: liveness, that replica's OWN live p99 +
    request count (off its raw window wire), staleness, scrape cost."""
    from ..telemetry import windows as _windows

    rows = []
    for r in view.replicas:
        p99 = reqs = None
        if r.doc is not None:
            for m in r.doc.get("metrics", ()):
                if m.get("name") == "serving_request_window_seconds":
                    wire = m.get("wire") or {}
                    try:
                        p99 = _windows.quantile_of_wire(wire, 0.99)
                        reqs = int(wire.get("count", 0))
                    except (ValueError, TypeError, KeyError):
                        pass
                    break
        rows.append((
            r.endpoint,
            r.outcome,
            "-" if p99 is None else f"{p99 * 1000:.1f}ms",
            "-" if reqs is None else str(reqs),
            ("-" if r.staleness_s is None
             else f"{r.staleness_s:.0f}s"),
            f"{r.elapsed_s * 1000:.0f}ms",
        ))
    header = ("REPLICA", "STATE", "p99", "REQS", "STALE", "SCRAPE")
    widths = [
        max(len(header[i]), *(len(r[i]) for r in rows)) if rows
        else len(header[i])
        for i in range(len(header))
    ]
    lines = ["  ".join(h.ljust(w) for h, w in zip(header, widths))]
    for r in rows:
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    return lines


def _fleet_window_rows(view) -> list[str]:
    """The MERGED windowed quantile series — the fleet-wide sibling of
    `dsst top`'s per-process windows section."""
    rows = []
    for fam in view.registry.families():
        if fam.kind != "window":
            continue
        for labels, sample in fam._series():
            label_txt = ",".join(
                f"{k}={v}" for k, v in sorted(labels.items())
            )
            name = fam.name + (f"{{{label_txt}}}" if label_txt else "")
            cells = " ".join(
                f"p{float(q) * 100:g}="
                + ("-" if v is None else f"{v * 1000:.2f}ms")
                for q, v in sorted(sample.get("quantiles", {}).items())
            )
            rows.append(
                f"  {name:<44} {cells}  n={sample.get('count', 0)}"
            )
    return rows


def _fleet_doc(view) -> dict:
    """The fleet status document (--json shape): per-replica outcomes
    plus the merged SLO judgment."""
    return {
        "version": 1,
        "ts": round(view.ts, 3),
        "up": view.up,
        "replicas": [
            {
                "endpoint": r.endpoint,
                "up": r.up,
                "outcome": r.outcome,
                "elapsed_ms": round(r.elapsed_s * 1000, 1),
                "staleness_s": (
                    round(r.staleness_s, 1)
                    if r.staleness_s is not None else None
                ),
                **({"error": r.error} if r.error else {}),
            }
            for r in view.replicas
        ],
        "merged_series": view.merged_series,
        "slo": view.slo,
    }


def _fleet_frame(agg, view, *, windows: bool = False) -> list[str]:
    lines = [
        f"dsst fleet — {len(agg.endpoints)} endpoint(s), "
        f"{view.up} up  {time.strftime('%H:%M:%S')}",
        "",
    ]
    lines.extend(_fleet_replica_rows(view))
    lines.append("")
    lines.extend(_slo_render_text(view.slo))
    if windows:
        rows = _fleet_window_rows(view)
        if rows:
            lines.append("")
            lines.append("fleet windows (merged):")
            lines.extend(rows)
    return lines


def _cmd_slo_status(args: argparse.Namespace) -> int:
    if args.fleet:
        agg = _fleet_aggregator(args)
        view = agg.scrape()
        if args.json:
            print(json.dumps(_fleet_doc(view), indent=1))
        else:
            for line in _fleet_frame(agg, view):
                print(line)
        return 0
    doc = _slo_fetch_status(args)
    if doc is None:
        return 2
    if args.json:
        print(json.dumps(doc, indent=1))
    else:
        for line in _slo_render_text(doc):
            print(line)
    return 0


def _cmd_slo_check(args: argparse.Namespace) -> int:
    if args.fleet:
        from ..telemetry import federation

        agg = _fleet_aggregator(args)
        view = agg.scrape()
        if view.up == 0:
            print("dsst slo: no replica answered the fleet scrape",
                  file=sys.stderr)
            return 2
        # One-shot judgment: a fresh state machine has had no cycles
        # to debounce pending→firing, so "burning" is the raw
        # two-window condition (federation.burning) — plus anything
        # already firing in the merged judgment.
        bad = federation.burning(view.slo)
        if args.strict:
            bad = sorted(set(bad) | {
                o["name"] for o in view.slo.get("objectives", [])
                if o.get("state") == "pending"
            })
        if args.json:
            print(json.dumps({
                **_fleet_doc(view),
                "ok": not bad,
                "failing": bad,
            }, indent=1))
        else:
            for line in _fleet_frame(agg, view):
                print(line)
            print("fleet slo check: "
                  + ("OK" if not bad else "FAILING " + ", ".join(bad)))
        return 1 if bad else 0
    doc = _slo_fetch_status(args)
    if doc is None:
        return 2
    bad = list(doc.get("firing", []))
    if args.strict:
        bad += [
            o["name"] for o in doc.get("objectives", [])
            if o.get("state") == "pending"
        ]
    if args.json:
        print(json.dumps({
            "version": doc.get("version", 1),
            "ok": not bad,
            "failing": sorted(set(bad)),
            "objectives": doc.get("objectives", []),
        }, indent=1))
    else:
        for line in _slo_render_text(doc):
            print(line)
        print("slo check: "
              + ("OK" if not bad else "FAILING " + ", ".join(sorted(set(bad)))))
    return 1 if bad else 0


def _cmd_slo_watch(args: argparse.Namespace) -> int:
    frames = 0
    # ONE aggregator across frames: the fleet alert state machine and
    # staleness clocks must persist or pending can never reach firing.
    agg = _fleet_aggregator(args) if args.fleet else None
    try:
        while True:
            if agg is not None:
                view = agg.scrape()
                print("\x1b[2J\x1b[H", end="")
                for line in _fleet_frame(agg, view):
                    print(line)
            else:
                doc = _slo_fetch_status(args)
                if doc is None:
                    return 2
                print("\x1b[2J\x1b[H", end="")
                print(f"dsst slo watch — {args.report or args.url}  "
                      f"{time.strftime('%H:%M:%S')}")
                for line in _slo_render_text(doc):
                    print(line)
            frames += 1
            if args.iterations and frames >= args.iterations:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def register_top(sub: argparse._SubParsersAction) -> None:
    tp = sub.add_parser(
        "top",
        help="live terminal view of a serving process: windowed "
        "latency quantiles, SLO budget remaining, firing alerts, and "
        "the scheduler/feeder gauges, fused from /slo + /metrics",
    )
    tp.add_argument(
        "--url", default="http://127.0.0.1:8008", metavar="URL",
        help="the dsst serve process to watch",
    )
    _add_fleet_args(tp)
    tp.add_argument("--interval", type=float, default=2.0,
                    metavar="SECONDS")
    tp.add_argument(
        "--once", action="store_true",
        help="print one frame and exit (scripting/tests)",
    )
    tp.set_defaults(fn=_cmd_top)


def _top_parse_metrics(text: str) -> tuple[dict, dict]:
    """Prometheus text → (plain series, labeled series).

    ``plain`` maps bare series names to floats; ``labeled`` maps name →
    list of ``(label_dict, value)``.
    """
    import re

    plain: dict[str, float] = {}
    labeled: dict[str, list] = {}
    label_re = re.compile(r'(\w+)="([^"]*)"')
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        name, _, value_s = line.rpartition(" ")
        name = name.strip()
        try:
            value = float(value_s)
        except ValueError:
            continue
        if "{" in name:
            base, _, rest = name.partition("{")
            labels = dict(label_re.findall(rest))
            labeled.setdefault(base, []).append((labels, value))
        else:
            plain[name] = value
    return plain, labeled


_TOP_GAUGES = (
    "serving_queue_depth",
    "admission_service_rate_ewma",
    "admission_est_queue_wait_ms",
    "slo_alerts_firing",
)


def _top_frame(url: str) -> list[str]:
    doc = _slo_http_json(url, "/slo")
    import http.client

    host, port = _slo_parse_url(url)
    conn = http.client.HTTPConnection(host, port, timeout=10)
    try:
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        text = resp.read().decode()
    finally:
        conn.close()
    plain, labeled = _top_parse_metrics(text)

    lines = [f"dsst top — {url}  {time.strftime('%H:%M:%S')}", ""]
    lines.extend(_slo_render_text(doc))
    lines.append("")
    # Windowed quantile series: every summary family on /metrics (the
    # window kind renders quantile-labeled samples). The _count join
    # must follow the same label split: a labeled family's _count line
    # carries the labels too, so it parses into `labeled`, keyed by
    # the identical non-quantile label tuple.
    labeled_counts: dict[tuple[str, tuple], float] = {}
    for lname, series in labeled.items():
        if not lname.endswith("_count"):
            continue
        for labels, value in series:
            labeled_counts[
                (lname[: -len("_count")],
                 tuple(sorted(labels.items())))
            ] = value
    window_rows = []
    for base, series in sorted(labeled.items()):
        by_labels: dict[tuple, dict] = {}
        for labels, value in series:
            q = labels.get("quantile")
            if q is None:
                continue
            rest = tuple(
                sorted((k, v) for k, v in labels.items()
                       if k != "quantile")
            )
            by_labels.setdefault(rest, {})[q] = value
        for rest, qs in sorted(by_labels.items()):
            label_txt = ",".join(f"{k}={v}" for k, v in rest)
            name = base + (f"{{{label_txt}}}" if label_txt else "")
            cells = " ".join(
                f"p{float(q) * 100:g}="
                + ("-" if v != v else f"{v * 1000:.2f}ms")
                for q, v in sorted(qs.items())
            )
            count = (
                labeled_counts.get((base, rest)) if rest
                else plain.get(f"{base}_count")
            )
            window_rows.append(
                f"  {name:<44} {cells}"
                + (f"  n={count:g}" if count is not None else "")
            )
    if window_rows:
        lines.append("windows:")
        lines.extend(window_rows)
        lines.append("")
    gauge_cells = [
        f"{g}={plain[g]:g}" for g in _TOP_GAUGES if g in plain
    ]
    if gauge_cells:
        lines.append("gauges: " + "  ".join(gauge_cells))
    return lines


def _cmd_top(args: argparse.Namespace) -> int:
    # Fleet mode holds ONE aggregator across frames (persistent alert
    # state machine + staleness clocks), and its frame adds the merged
    # fleet windows under the per-replica columns.
    agg = _fleet_aggregator(args) if args.fleet else None
    try:
        while True:
            if agg is not None:
                view = agg.scrape()
                frame = _fleet_frame(agg, view, windows=True)
            else:
                try:
                    frame = _top_frame(args.url)
                except (OSError, ValueError) as e:
                    print(f"dsst top: cannot scrape {args.url}: {e}",
                          file=sys.stderr)
                    return 2
            if not args.once:
                print("\x1b[2J\x1b[H", end="")
            for line in frame:
                print(line)
            if args.once:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def register_all(sub: argparse._SubParsersAction) -> None:
    register_datagen(sub)
    register_forecast(sub)
    register_eda(sub)
    register_ingest(sub)
    register_train(sub)
    register_predict(sub)
    register_export(sub)
    register_serve(sub)
    register_serve_lm(sub)
    register_lm(sub)
    register_hpo(sub)
    register_trial_worker(sub)
    register_checkpoints(sub)
    register_quarantine(sub)
    register_runs(sub)
    register_chaos(sub)
    register_telemetry(sub)
    register_trace(sub)
    register_lint(sub)
    register_audit(sub)
    register_sanitize(sub)
    register_bench(sub)
    register_slo(sub)
    register_top(sub)
    from .pipeline import register_pipeline

    register_pipeline(sub)


if __name__ == "__main__":  # pragma: no cover
    from .cli import main

    sys.exit(main())
