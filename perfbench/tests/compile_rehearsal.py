"""Compile each cell's programs at real size for a described v5e:2x2.

    JAX_PLATFORMS=cpu python3 perfbench/tests/compile_rehearsal.py [train1 train4 ref1 ref4 serve]

No chip is attached and nothing runs: this shows what the chip's
compiler refuses and what ``memory_analysis()`` counts, one program at a
time.  A compile that passes is not a chip run.
"""

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

import harness  # noqa: E402
import weights  # noqa: E402

GB = 1e9


def report(name, compiled, t0):
    m = compiled.memory_analysis()
    print(json.dumps({
        "program": name, "compile_s": round(time.time() - t0, 1),
        "argument_GB": round(m.argument_size_in_bytes / GB, 3),
        "output_GB": round(m.output_size_in_bytes / GB, 3),
        "alias_GB": round(m.alias_size_in_bytes / GB, 3),
        "temp_GB": round(m.temp_size_in_bytes / GB, 3),
        "code_MB": round(m.generated_code_size_in_bytes / 1e6, 1),
    }), flush=True)
    return compiled


def train(chips, topo):
    from adapters import resnet as adapter
    from dss_ml_at_scale_tpu.parallel.trainer import make_train_step

    cell = harness.load_cell("resnet50_train_predecoded")
    cfg = cell.config
    defaults = adapter.program_defaults()
    task = adapter.build_task(cfg, defaults)
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    rep, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    batch = 212 * chips
    shapes = adapter.variable_shapes(task, cfg["crop"])
    variables = weights.nest({p: jax.ShapeDtypeStruct(s, jnp.float32)
                              for p, s in shapes.items()})
    state = jax.eval_shape(task.state_from_variables, variables)
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), state)
    shardings = jax.tree_util.tree_map(lambda _: rep, state)
    b = {"image": jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32,
                                       sharding=data),
         "label": jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=data)}
    t0 = time.time()
    c = report(f"train_step x{chips}",
               make_train_step(task, shardings, rep).lower(state, b).compile(),
               t0)
    text = c.as_text()
    print(json.dumps({"all_reduce_ops": text.count("all-reduce("),
                      "all_reduce_start": text.count("all-reduce-start(")}))


def ref(chips, topo):
    import functools
    from references import resnet as reference

    cell = harness.load_cell("resnet50_train_predecoded")
    cfg = cell.config
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    rep, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    batch = 212 * chips
    p = {k[len("params/"):]: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
         for k, s in reference.param_shapes(cfg).items()
         if k.startswith("params/")}
    step = jax.jit(functools.partial(reference.train_step, cfg=cfg, lr=1e-5,
                                     quant=lambda a: a),
                   donate_argnums=(0, 1, 2))
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        c = step.lower(
            p, p, p, jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32,
                                 sharding=data),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=data)).compile()
    report(f"reference_step x{chips}", c, t0)


def serve(topo):
    from adapters import transformer_lm as adapter
    from dss_ml_at_scale_tpu.serving.lm import kvcache
    from jax.sharding import SingleDeviceSharding

    cell = harness.load_cell("cerebras_gpt_1p3b_serve_chat")
    cfg, server = cell.config, cell.traffic["server"]
    one = SingleDeviceSharding(topo.devices[0])
    model = adapter.build_model(cfg, server)
    shapes = adapter.variable_shapes(model, 128)
    variables = weights.nest({p: jax.ShapeDtypeStruct(s, jnp.float32,
                                                      sharding=one)
                              for p, s in shapes.items()})

    def arena(slots):
        shape = (slots, cfg["num_attention_heads"], server["max_len"],
                 cfg["head_size"])
        leaf = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
        return tuple({"k": leaf, "v": leaf}
                     for _ in range(cfg["num_hidden_layers"]))

    slots = server["slots"]
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    t0 = time.time()
    report("slot_decode", jax.jit(
        kvcache.slot_decode, static_argnums=0, donate_argnums=(3,)).lower(
            model, variables, vec, arena(slots), vec).compile(), t0)
    for bucket in server["prefill_buckets"][-1:]:
        tok = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one)
        t0 = time.time()
        report(f"prefill_bucket {bucket}", jax.jit(
            kvcache.prefill_bucket, static_argnums=0,
            donate_argnums=(3,)).lower(model, variables, tok,
                                       arena(1)).compile(), t0)


def main():
    which = sys.argv[1:] or ["train1", "train4", "ref1", "ref4", "serve"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for w in which:
        try:
            {"train1": lambda: train(1, topo), "train4": lambda: train(4, topo),
             "ref1": lambda: ref(1, topo), "ref4": lambda: ref(4, topo),
             "serve": lambda: serve(topo)}[w]()
        except Exception as e:  # a refusal is the finding: print and go on
            print(json.dumps({"program": w, "refused": f"{type(e).__name__}: "
                              f"{str(e)[:600]}"}), flush=True)


if __name__ == "__main__":
    main()
