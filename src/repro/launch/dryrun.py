import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: prove every (arch × shape × mesh) lowers AND compiles.

The three lines above MUST stay first: jax locks the platform and the
device count at first initialization, and the production meshes (16×16
and 2×16×16) need 512 placeholder host devices. Pinning the CPU platform
keeps this process off any attached TPU. Do not set these flags anywhere
global — tests and benches must see their own devices.

For each combination this entrypoint:
  1. builds the production mesh (single- or multi-pod),
  2. constructs sharded ShapeDtypeStruct stand-ins for every input
     (params / optimizer state / error-feedback / batch, or decode caches),
  3. jits the step with those shardings, .lower().compile(),
  4. prints compiled.memory_analysis() (bytes/device) and cost_analysis()
     (FLOPs / bytes for §Roofline), plus the collective-op byte census parsed
     from the partitioned HLO text.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --arch yi-6b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --sweep --json-out results.json
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import configs
from repro.configs.shapes import SHAPES, applicable, input_specs
from repro.dist import step as step_lib
from repro.dist.gradcomp import GradCompConfig
from repro.dist.sharding import batch_specs
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.optimizer import adamw, sgd


def _sharded_batch_specs(cfg, shape, mesh):
    batch = input_specs(cfg, shape)
    specs = batch_specs(batch, mesh)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        batch, specs)


def build_lowered(cfg, shape, mesh, gc: GradCompConfig, opt_name: str):
    """Returns (lowered, model_flops)."""
    if shape.mode == "train":
        opt = (adamw(1e-4, weight_decay=0.1) if opt_name == "adamw"
               else sgd(1e-2, momentum=0.9))
        if gc.strategy == "alltoall_zero1":
            tstep = step_lib.make_zero_train_step(cfg, opt, gc, mesh,
                                                  gather_dtype=jnp.bfloat16)
            params, opt_state, ef = step_lib.zero_state_specs(cfg, opt, gc,
                                                              mesh)
        else:
            tstep = step_lib.make_train_step(cfg, opt, gc, mesh)
            params, opt_state, ef = step_lib.train_state_specs(cfg, opt, gc,
                                                               mesh)
        batch = _sharded_batch_specs(cfg, shape, mesh)
        lowered = tstep.lower(params, opt_state, ef, batch)
        tokens = shape.global_batch * shape.seq_len
        return lowered, hlo_analysis.model_flops_train(cfg, tokens)

    if shape.mode == "prefill":
        def fwd(params, batch):
            h, positions, _ = model_lib._embed_inputs(cfg, params, batch)
            h, _ = model_lib.forward_hidden(cfg, params, h, positions)
            return (h[:, -1] @ params["head"]).astype(jnp.float32)

        from repro.dist.sharding import param_specs
        params_shape = jax.eval_shape(
            lambda: model_lib.init_params(jax.random.key(0), cfg))
        pspecs = param_specs(params_shape, mesh.shape.get("model", 1))
        params = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            params_shape, pspecs)
        batch = _sharded_batch_specs(cfg, shape, mesh)
        lowered = jax.jit(fwd).lower(params, batch)
        toks = shape.global_batch * shape.seq_len
        return lowered, hlo_analysis.model_flops_train(cfg, toks) / 3.0  # fwd

    if shape.mode == "decode":
        sstep = step_lib.make_serve_step(cfg, mesh)
        params, state, tokens = step_lib.serve_state_specs(
            cfg, mesh, shape.global_batch, shape.seq_len)
        lowered = sstep.lower(params, state, tokens)
        return lowered, hlo_analysis.model_flops_decode(cfg,
                                                        shape.global_batch)

    raise ValueError(shape.mode)


def run_combo(arch: str, shape_name: str, multi_pod: bool,
              gc: GradCompConfig, opt_name: str = "adamw",
              verbose: bool = True, kv_quant: int | None = None) -> dict:
    cfg = configs.get(arch)
    if kv_quant:
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_quant_bits=kv_quant)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "mode": shape.mode, "strategy": gc.strategy, "bits": gc.bits}
    ok, reason = applicable(cfg, shape)
    if not ok:
        rec.update(status="SKIP", reason=reason)
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        # `with mesh:` provides the device context; set_mesh additionally
        # publishes the abstract mesh so in-model sharding hints
        # (with_sharding_constraint on raw PartitionSpecs, e.g. the MoE
        # expert-parallel dispatch buffer) resolve during tracing.
        jax.set_mesh(mesh)
        with mesh:
            lowered, model_flops = build_lowered(cfg, shape, mesh, gc,
                                                 opt_name)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            text = compiled.as_text()
        n_dev = mesh.size
        roof = hlo_analysis.roofline_terms(cost, text, model_flops, n_dev)
        from repro.launch import hlo_static
        coll = hlo_static.analyze(text)
        rec.update(
            xla_cost={"flops": cost.get("flops"),
                      "bytes_accessed": cost.get("bytes accessed")},
            status="OK",
            compile_s=round(time.time() - t0, 1),
            num_devices=n_dev,
            memory={k: getattr(mem, k) for k in
                    ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes")},
            bytes_per_device=mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes,
            roofline=roof.table_row(),
            collectives=coll.collectives_by_kind,
        )
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] OK "
                  f"({rec['compile_s']}s compile)")
            print(f"  memory/device: args={mem.argument_size_in_bytes/2**30:.2f}"
                  f"GiB out={mem.output_size_in_bytes/2**30:.2f}GiB "
                  f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB")
            print(f"  flops/device={roof.flops_per_device:.3e} "
                  f"hbm_bytes={roof.hbm_bytes_per_device:.3e} "
                  f"coll_bytes={roof.collective_bytes_per_device:.3e}")
            print(f"  terms: compute={roof.compute_s*1e3:.2f}ms "
                  f"memory={roof.memory_s*1e3:.2f}ms "
                  f"collective={roof.collective_s*1e3:.2f}ms "
                  f"→ {roof.dominant}-bound")
            if roof.useful_flops_ratio:
                print(f"  MODEL_FLOPS/HLO_FLOPS = "
                      f"{roof.useful_flops_ratio:.3f}")
            print(f"  collectives: {coll.collectives_by_kind}")
    except Exception as e:  # noqa: BLE001 — a failed combo is a data point
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   compile_s=round(time.time() - t0, 1))
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] FAIL: "
                  f"{rec['error']}")
            traceback.print_exc()
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=configs.ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sweep", action="store_true",
                    help="all (arch × shape) on the selected mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--bits", type=int, default=4, choices=(1, 2, 4, 8))
    ap.add_argument("--strategy", default="allgather_packed",
                    choices=("psum", "psum_decoded", "allgather_packed",
                             "alltoall_zero1"))
    ap.add_argument("--opt", default="adamw", choices=("adamw", "sgd"))
    ap.add_argument("--kv-quant", type=int, default=None, choices=(4, 8),
                    help="NDSC-packed KV cache bits for decode shapes")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    gc = GradCompConfig(bits=args.bits, strategy=args.strategy)
    records = []
    if args.sweep:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for arch in configs.ARCH_NAMES:
            for shape_name in SHAPES:
                for mp in meshes:
                    records.append(run_combo(arch, shape_name, mp, gc,
                                             args.opt))
                    jax.clear_caches()
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required (or --sweep)")
        records.append(run_combo(args.arch, args.shape, args.multi_pod, gc,
                                 args.opt, kv_quant=args.kv_quant))

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records → {args.json_out}")
    failures = [r for r in records if r["status"] == "FAIL"]
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
