"""Driver of the training cells: one ``Trainer.fit`` from set-up to the
window's end.

Set-up builds one object (the program's trainer with its compiled step
and its state) and drives it through its first steps on rows that all
differ; the same call goes on into the window.  The benchmark sees the
step through a probe put around what ``make_train_step`` returns: the
program's jitted step is called as it is, the probe reads losses and
norms off its outputs after the first steps, marks the window's start
once the warm-up steps are done, and takes the trace.  The window ends
where ``fit`` itself waits for the last step's state.
"""

from __future__ import annotations

import importlib
import itertools
import json
import os
import shutil
import statistics
import tempfile
import time

import numpy as np

import harness
import trace as tracemod
import weights

ADAM_B1 = 0.9   # optax.adam's default, which `dsst train` does not change


class Feed:
    """The iterable ``fit`` trains from: the program's reader (or the
    pool of host batches) until the deadline; keeps the first batches."""

    def __init__(self, source, n_keep: int, copy: bool):
        self.source = iter(source)
        self.n_keep, self.copy = n_keep, copy
        self.kept: list = []
        self.deadline: float | None = None

    def __iter__(self):
        return self

    def __next__(self):
        if self.deadline is not None and time.perf_counter() >= self.deadline:
            raise StopIteration
        batch = next(self.source)
        if len(self.kept) < self.n_keep:
            self.kept.append({k: np.array(v) if self.copy else v
                              for k, v in batch.items()})
        return batch


class StepProbe:
    """Stands where the program's jitted train step stands."""

    def __init__(self, jitted, plan):
        self.jitted, self.plan = jitted, plan
        self.calls = 0

    def __getattr__(self, name):          # _cache_size and the like
        return getattr(self.jitted, name)

    def __call__(self, state, batch):
        import jax
        import jax.numpy as jnp

        plan = self.plan
        keep = plan.faults.get("keep_rows_fraction")
        if keep:
            batch = {k: v[: int(len(v) * keep)] for k, v in batch.items()}
        held = None
        if plan.faults.get("state_unchanged"):
            held = jax.tree_util.tree_map(jnp.copy, state)
        new_state, metrics = self.jitted(state, batch)
        if held is not None:
            new_state = held
        self.calls += 1
        plan.after_step(self.calls, new_state, metrics)
        return new_state, metrics


class Plan:
    """The run's course: first steps, warm-up, window, trace."""

    def __init__(self, *, n_check, warm, seconds, trace, trace_seconds,
                 trace_dir, feed, p0, faults):
        self.n_check, self.warm, self.seconds = n_check, warm, seconds
        self.trace, self.trace_seconds = trace, trace_seconds
        self.trace_dir = trace_dir
        self.feed, self.p0, self.faults = feed, p0, faults
        self.losses: list = []
        self.mu1 = self.mu1_full = self.change = None
        self.t0 = self.t1 = self.wall0 = None
        self.counters0 = self.counters1 = None
        self.steps_at_t0 = self.steps_at_t1 = 0
        self.tracing = False
        self.traced = None
        self.last_state = None

    @staticmethod
    def _norms(tree):
        import jax
        import jax.numpy as jnp

        return jax.jit(lambda t: jax.tree_util.tree_map(
            lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
            t))(tree)

    def after_step(self, n, state, metrics):
        import jax
        import jax.numpy as jnp

        self.last_state = state
        if n <= self.n_check:
            self.losses.append(metrics["train_loss"])
            if n == 1:
                adam = [x for x in jax.tree_util.tree_leaves(
                    state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                    if hasattr(x, "mu")]
                self.mu1 = self._norms(adam[0].mu)
                self.mu1_full = jax.tree_util.tree_map(jnp.copy, adam[0].mu)
            if n == self.n_check:
                diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
                    lambda x, y: x - y, a, b))(state.params, self.p0)
                self.change = self._norms(diff)
                self.p0 = None
        if n == self.n_check + self.warm:
            jax.block_until_ready(state.params)
            self.counters0 = harness.program_counters()
            self.steps_at_t0 = n
            self.wall0, self.t0 = time.time(), time.perf_counter()
            self.feed.deadline = self.t0 + self.seconds
            if self.trace:
                tracemod.start(self.trace_dir)
                self.tracing = True
                self.traced = [time.perf_counter(), None]
        elif self.tracing and (time.perf_counter() - self.traced[0]
                               >= self.trace_seconds):
            self.stop_trace()

    def stop_trace(self):
        import jax

        if self.tracing:
            jax.block_until_ready(self.last_state.params)
            self.traced[1] = time.perf_counter()
            tracemod.stop()
            self.tracing = False

    def close(self, steps):
        """Called by ``fit`` right after it waited for the last state."""
        self.t1 = time.perf_counter()
        self.steps_at_t1 = steps
        self.stop_trace()
        self.counters1 = harness.program_counters()


def _match_rows(kept, jpegs, labels, crop):
    """The table rows behind each delivered image, by the reference's own
    decode: among the rows with the delivered label, the nearest.  Returns
    the reference's batches and the widest pixel gap."""
    from concurrent.futures import ThreadPoolExecutor

    image_table = importlib.import_module("references.image_table")
    by_label: dict = {}
    for row, lab in enumerate(labels):
        by_label.setdefault(int(lab), []).append(row)
    decoded: dict = {}
    wanted = sorted({r for b in kept for lab in b["label"]
                     for r in by_label.get(int(lab), [])})
    with ThreadPoolExecutor(8) as pool:
        for row, img in zip(wanted, pool.map(
                lambda r: image_table.decode(jpegs[r], crop), wanted)):
            decoded[row] = img
    worst, batches = 0.0, []
    for b in kept:
        imgs = []
        for img, lab in zip(b["image"], b["label"]):
            rows = by_label.get(int(lab), [])
            if not rows:
                return None, float("inf")
            gaps = [float(np.max(np.abs(decoded[r] - img))) for r in rows]
            best = int(np.argmin(gaps))
            worst = max(worst, gaps[best])
            imgs.append(decoded[rows[best]])
        batches.append((np.stack(imgs), np.asarray(b["label"], np.int32)))
    return batches, worst


def _numbers(prog, ref, n_check):
    """The numbers compared, program (or a stand-in) against reference:
    each step's loss, and the gaps of the per-leaf norms of the first
    gradient and of the parameters' change, by the worst leaf and by the
    median leaf."""
    out = {}
    for i in range(n_check):
        out[f"loss_{i + 1}"] = abs(prog["loss"][i] - ref["loss"][i]) / abs(
            ref["loss"][i])
    grad = harness.gaps_of_norms(prog["grad"], ref["grad"])
    # Leaves whose gradient is nought to rounding move under Adam by
    # round-off alone: left out by a rule on the reference's gradient.
    floor = 1e-3 * statistics.median(ref["grad"].values())
    moved = {k for k, g in ref["grad"].items() if g >= floor}
    change = harness.gaps_of_norms(prog["change"], ref["change"], keep=moved)
    out["grad_first"] = max(grad.values())
    out["grad_first_median"] = statistics.median(grad.values())
    # The gaps of norms are second order in unbiased rounding noise and so
    # cannot tell one precision from the next (PERF.md, Findings); the
    # size of the difference itself is first order.  By the median leaf.
    floor_n = statistics.median(ref["grad"].values())
    direction = {
        k: float(np.linalg.norm(prog["grad_full"][k].astype(np.float32)
                                - ref["grad_full"][k]))
        / max(ref["grad"][k], floor_n) for k in ref["grad"]}
    out["grad_first_direction"] = statistics.median(direction.values())
    # ... and by the leaf where it is least: the classifier's bias, whose
    # gradient is the mean of softmax(logits) - labels and so needs the
    # forward pass alone.  The backward pass amplifies rounding until the
    # median leaf reads a tenth in bfloat16; this leaf reads what the
    # logits' precision is, and tells bfloat16 from fp8 by 13 times.
    out["grad_first_direction_least"] = min(direction.values())
    out["change_after"] = max(change.values())
    out["change_after_median"] = statistics.median(change.values())
    return out, {"grad_first": max(grad, key=grad.get),
                 "change_after": max(change, key=change.get),
                 "grad_first_direction_least": min(direction,
                                                   key=direction.get),
                 "leaves_left_out": len(ref["grad"]) - len(moved),
                 "per_leaf": {"grad": grad, "change": change,
                              "direction": direction,
                              "ref_grad": ref["grad"]}}


def run(cell, *, seed, seconds, trace, t_start, require_chip, faults,
        variants=()):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices, device = harness.find_devices(cell.chips, require_chip)
    compiles = harness.CompileCounter()
    from dss_ml_at_scale_tpu.parallel import trainer as trainer_mod
    from dss_ml_at_scale_tpu.runtime import enable_compile_cache, make_mesh

    cache_dir = enable_compile_cache()
    cfg, tr = cell.config, cell.traffic
    adapter = importlib.import_module(f"adapters.{cfg['family']}")
    reference = importlib.import_module(f"references.{cfg['family']}")
    lowprec = importlib.import_module("references.lowprec")
    datagen = importlib.import_module("datagen")
    defaults = adapter.program_defaults()
    mesh = make_mesh(devices=devices)
    batch = tr["batch_per_chip"] * cell.chips
    crop, classes = cfg["crop"], cfg["num_classes"]
    n_check = tr["check_steps"]
    task = adapter.build_task(cfg, defaults)

    shapes = reference.param_shapes(cfg)
    if shapes != adapter.variable_shapes(task, crop):
        raise RuntimeError("the reference and the program disagree on the "
                           "model's variables")
    overrides = cfg.get("init_overrides")
    flat = weights.make(shapes, seed, overrides=overrides)
    p0 = weights.nest({k: jnp.copy(v) for k, v in flat.items()
                       if k.startswith("params/")})["params"]
    state = adapter.initial_state(task, flat)
    del flat

    table_dir = reader_cm = None
    jpegs = row_labels = None
    if tr["source"] == "delta_table":
        table_dir = tempfile.mkdtemp(prefix="perfbench_table_")
        shutil.rmtree(table_dir)
        jpegs, row_labels = datagen.table_rows(
            seed, tr["table_rows"], classes, tr["image_size"],
            tr["jpeg_quality"])
        adapter.write_table(table_dir, jpegs, row_labels)
        reader_cm = adapter.table_reader(table_dir, defaults, batch=batch,
                                         crop=crop)
        source = reader_cm.__enter__()
        warm = max(tr["warm_steps"],
                   defaults.queue_size + defaults.feeder_depth + 2)
    else:
        images = datagen.image_batches(seed, tr["pool_batches"], batch, crop)
        labs = datagen.labels(seed, tr["pool_batches"] * batch, classes)
        pool = [{"image": img, "label": labs[i * batch:(i + 1) * batch]}
                for i, img in enumerate(images)]
        source = itertools.cycle(pool)
        warm = tr["warm_steps"]
    feed = Feed(source, n_check, copy=tr["source"] == "delta_table")
    trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_") if trace else None
    plan = Plan(n_check=n_check, warm=warm, seconds=seconds, trace=trace,
                trace_seconds=tr["trace_seconds"], trace_dir=trace_dir,
                feed=feed, p0=p0, faults=faults)
    del p0
    probe_box: list = []
    make_step = trainer_mod.make_train_step

    def probed_make_train_step(*a, **kw):
        probe_box.append(StepProbe(make_step(*a, **kw), plan))
        return probe_box[-1]

    trainer = adapter.make_trainer(defaults, mesh)
    trainer_mod.make_train_step = probed_make_train_step
    try:
        result = trainer.fit(
            task, feed, state=state,
            epoch_callback=lambda _s: plan.close(probe_box[-1].calls))
    finally:
        trainer_mod.make_train_step = make_step
        if reader_cm is not None:
            reader_cm.__exit__(None, None, None)
        if table_dir is not None:
            shutil.rmtree(table_dir, ignore_errors=True)
    if plan.t0 is None or plan.t1 is None:
        raise RuntimeError("the run ended before its window opened")
    peak = harness.memory_peak_bytes(devices)
    steps = plan.steps_at_t1 - plan.steps_at_t0
    in_window = compiles.inside(plan.t0, plan.t1)
    spans = harness.program_spans(plan.wall0,
                                  plan.wall0 + (plan.t1 - plan.t0))
    prog = {
        "loss": [float(x) for x in plan.losses],
        "grad": {k: float(v) / (1 - ADAM_B1) for k, v in weights.flatten(
            jax.device_get(plan.mu1)).items()},
        "change": {k: float(v) for k, v in weights.flatten(
            jax.device_get(plan.change)).items()},
        "grad_full": {k: np.asarray(v) / (1 - ADAM_B1) for k, v in
                      weights.flatten(jax.device_get(plan.mu1_full)).items()},
    }
    final_step = int(result.state.step)
    del result, state
    plan.last_state = plan.mu1 = plan.mu1_full = plan.change = None

    # -- the reference, once the window has closed and the state is freed
    t_ref = time.perf_counter()
    numbers: dict = {}
    if tr["source"] == "delta_table":
        batches, numbers["pixel_gap"] = _match_rows(feed.kept, jpegs,
                                                    row_labels, crop)
    else:
        batches = [(b["image"], b["label"]) for b in feed.kept]
    lr = defaults.learning_rate
    ref_mesh = Mesh(np.asarray(devices), ("data",))
    shard = dict(batch_sharding=NamedSharding(ref_mesh, P("data")))
    replicated = NamedSharding(ref_mesh, P())

    def ref_params():
        made = weights.make({k: s for k, s in shapes.items()
                             if k.startswith("params/")}, seed,
                            sharding=replicated, overrides=overrides)
        return {k[len("params/"):]: v for k, v in made.items()}

    readings, where, per_leaf = {}, {}, {}
    if batches is not None:
        ref = reference.follow(ref_params(), batches, cfg=cfg, lr=lr, **shard)
        got, where = _numbers(prog, ref, n_check)
        per_leaf["program"] = where.pop("per_leaf")
        numbers.update(got)
        for variant in variants:
            if variant == "control_fp8":
                alt = reference.follow(ref_params(), batches, cfg=cfg, lr=lr,
                                       quant=lowprec.fp8, **shard)
            elif variant.startswith("fault_rows_"):
                # the reference in the program's place, fed only the first
                # 1/k of every batch: half the batch left out (k = 2), or
                # one chip's rows with no exchange (k = chips)
                k = int(variant.rsplit("_", 1)[1])
                cut = [(im[: len(im) // k], lb[: len(lb) // k])
                       for im, lb in batches]
                kw = shard if (batch // k) % cell.chips == 0 else {}
                alt = reference.follow(ref_params(), cut, cfg=cfg, lr=lr,
                                       **kw)
            else:
                raise ValueError(f"unknown variant {variant!r}")
            # The stand-in is held to the cell's limits by the same
            # comparison as the program (it was fed the program's pixels).
            stood, extra = _numbers(alt, ref, n_check)
            if "pixel_gap" in numbers:
                stood["pixel_gap"] = numbers["pixel_gap"]
            ok, table = harness.compare(stood, tr["limits"], quiet=True)
            readings[variant] = {"correct": ok, "compared": table,
                                 "numbers": stood}
            per_leaf[variant] = extra["per_leaf"]
            del alt
    else:
        numbers.update({k: float("inf") for k in tr["limits"]
                        if k != "pixel_gap"})   # a row matched no table row
    ref_seconds = time.perf_counter() - t_ref
    correct, compared = harness.compare(numbers, tr["limits"])

    window = harness.Window(
        cell=cell, t0=plan.t0, t1=plan.t1, wall0=plan.wall0, spans=spans,
        counters0=plan.counters0, counters1=plan.counters1,
        stats={"steps": steps, "samples": steps * batch, "batch": batch,
               "chips": cell.chips},
        device_kind=device["kind"],
        traced=tuple(plan.traced) if plan.traced else None)
    tables = None
    if trace:
        tables = tracemod.load_xplane(tracemod.find_xplane(trace_dir),
                                      spans=spans)
        window.tables = tables
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = harness.process_age(t_start) - (time.perf_counter() - plan.t0)
    device["memory_peak_bytes"] = peak
    harness.log(json.dumps({
        "cell": cell.name, "seed": seed, "steps_in_window": steps,
        "window_s": window.seconds, "final_step": final_step,
        "warm_steps": warm, "compiles_in_window": in_window,
        "compile_cache": harness.cache_report(cache_dir),
        "compile_cache_events": compiles.events,
        "compile_seconds": round(sum(s for _, s in compiles.compiles), 2),
        "memory_peak_bytes": peak, "host_cores": os.cpu_count(),
        "memory_stats": devices[0].memory_stats(),
        "reference_seconds": round(ref_seconds, 2), "worst_leaves": where,
        "program_defaults": {k: getattr(defaults, k) for k in (
            "workers", "queue_size", "feeder_depth", "image_dtype",
            "shuffle", "decode_backend", "fused_bn", "learning_rate")},
    }, default=str))
    if in_window:
        raise RuntimeError(f"{in_window} compilations inside the window")
    return harness.finish(
        cell, trace=trace, correct=correct, compared=compared,
        attempted=steps, failed=0,
        end_to_end={tr["reports"]: steps * batch / window.seconds,
                    "setup_s": setup_s},
        window=window, device=device, tables=tables,
        busy_window=(plan.traced[1] - plan.traced[0]) if plan.traced else None,
        extra={"readings": readings, "per_leaf": per_leaf}
        if readings else None)

