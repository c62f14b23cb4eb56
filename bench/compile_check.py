"""Compile each cell's train step for a described TPU v5e and print what
the compiler says it needs: no chip is used.

    JAX_PLATFORMS=cpu python bench/compile_check.py [cell ...] [--set k=v ...]

Each cell's step (`repro.dist.step.make_train_step`, as the harness builds
it) is lowered for the devices of a described `v5e:2x2` topology — one
device, or the cell's (4, 1) mesh — with the codec's Pallas kernels
compiled for the chip (not the CPU's interpreter), and compiled by the TPU
compiler. Prints `memory_analysis()` per device and the kernels found.
`--set num_layers=2` or `--set per_worker_batch=4` tries another size.
"""
import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_FORCE_PALLAS"] = "1"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from bench import cells  # noqa: E402

GIB = 2 ** 30
HBM_GIB = 15.75            # what the v5e compiler allows one program


def check(name: str, overrides: dict) -> dict:
    from jax.experimental import topologies
    from repro.dist import step as step_lib
    from repro.dist.gradcomp import GradCompConfig
    from repro.optimizer import adamw, warmup_cosine

    cell = cells.workload(name)
    cfg = cells.config(cell["config"])
    for k, v in overrides.items():
        (cfg if k in cfg else cell)[k] = type((cfg if k in cfg else cell)[k])(v)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    rows, cols = cell["mesh"]
    mesh = Mesh(np.asarray(topo.devices[:rows * cols]).reshape(rows, cols),
                ("data", "model"))
    o = cell["optimizer"]
    opt = adamw(warmup_cosine(o["lr"], o["warmup"], o["total"]), b1=o["b1"],
                b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
    gc = GradCompConfig(bits=cell["bits"], chunk=cell["chunk"],
                        strategy=cell["strategy"],
                        error_feedback=cell["error_feedback"],
                        keep_fraction=cell["keep_fraction"])
    model = cells.model_config(cfg)
    step = step_lib.make_train_step(model, opt, gc, mesh,
                                    clip_norm=o["clip_norm"])
    specs = step_lib.train_state_specs(model, opt, gc, mesh)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cell["per_worker_batch"] * rows, cell["seq_len"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, P("data")))}
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"      # compile the kernels, not the interpreter
    precision = cfg.get("matmul_precision", "default")
    try:
        with jax.default_matmul_precision(precision):
            compiled = step.lower(*specs, batch).compile()
    finally:
        jax.default_backend = real_backend
    ma = compiled.memory_analysis()
    kernels = compiled.as_text().count("custom_call_target=\"tpu_custom_call\"")
    params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(specs[0]))
    out = {"cell": name, "overrides": overrides, "params": params,
           "arguments_gib": ma.argument_size_in_bytes / GIB,
           "outputs_gib": ma.output_size_in_bytes / GIB,
           "temporaries_gib": ma.temp_size_in_bytes / GIB,
           "aliased_gib": ma.alias_size_in_bytes / GIB,
           "tpu_custom_calls": kernels}
    out["total_gib"] = (out["arguments_gib"] + out["outputs_gib"]
                        + out["temporaries_gib"] - out["aliased_gib"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--set", action="append", default=[],
                    help="key=value for the config or the cell")
    args = ap.parse_args(argv)
    overrides = dict(kv.split("=", 1) for kv in args.set)
    for name in args.cells or cells.workload_names():
        r = check(name, overrides)
        print(f"{r['cell']} {r['overrides'] or ''}: params {r['params']}; "
              f"per device {r['arguments_gib']:.3f} GiB arguments, "
              f"{r['outputs_gib']:.3f} outputs, {r['temporaries_gib']:.3f} "
              f"temporaries, {r['aliased_gib']:.3f} aliased = "
              f"{r['total_gib']:.3f} of {HBM_GIB}; "
              f"{r['tpu_custom_calls']} Pallas kernels", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
