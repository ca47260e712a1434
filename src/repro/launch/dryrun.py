"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell:
  lower + compile the step function (train_step for train shapes, prefill /
  decode steps for serving shapes) against ShapeDtypeStruct inputs on the
  production mesh, print memory_analysis() and cost_analysis(), derive the
  three roofline terms (launch/roofline.py + launch/hlo_cost.py), and write
  a JSON record under experiments/dryrun/.

Meshes: single-pod (16, 16) = 256 chips; multi-pod (2, 16, 16) = 512 chips.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --summarize
The --all driver runs each cell in a fresh subprocess (compile arenas are
per-process; a wedged cell cannot poison the sweep) and skips cells whose
JSON already exists (resumable).

The production mesh is 512 host (CPU) devices standing in for the pod:
run as a program, this module sets ``JAX_PLATFORMS=cpu`` and the
host-device count before JAX is imported, so on a TPU host it still builds
the 512-device mesh instead of finding the one local chip.
"""
import argparse
import json
import os
import subprocess
import sys
import time
import traceback

# the chip the production mesh models; its devices here are CPU stand-ins
TARGET_DEVICE_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

# Sharding-plan hints (bf16 moments, FSDP over the pod axis) are declared
# per-config: ModelConfig.opt_state_dtype / ModelConfig.fsdp_over_pod.


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str,
             *, seq_shard: bool = False, microbatches: int = 1,
             param_dtype: str = "", moe_groups: int = 0,
             moe_pin: str = "auto", moe_expert_axis: str = "model",
             remat: str = "", tag: str = "") -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import cell_supported, get_config
    from repro.dist import sharding_rules as SR
    from repro.dist.context import use_plan
    from repro.launch import specs as S
    from repro.launch.mesh import make_plan, make_production_mesh
    from repro.launch.roofline import build_report
    from repro.models import build_model
    from repro.models.config import SHAPES
    from repro.serve.engine import make_serve_step
    from repro.train import AdamWConfig, make_train_step
    from repro.train import optimizer as opt

    cfg = get_config(arch)
    if param_dtype:
        cfg = cfg.replace(param_dtype=param_dtype)
    if moe_groups and cfg.num_experts:
        cfg = cfg.replace(moe_groups=moe_groups)
    if remat:
        cfg = cfg.replace(remat=remat)
    shape = SHAPES[shape_name]
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "unknown",
        "kind": shape.kind,
        "variant": {
            "seq_shard": seq_shard,
            "microbatches": microbatches,
            "param_dtype": param_dtype or cfg.param_dtype,
            "tag": tag,
        },
    }
    supported, reason = cell_supported(cfg, shape)
    if not supported:
        record.update(status="SKIP", reason=reason)
        return record

    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = int(mesh.size)
    plan = make_plan(mesh, fsdp_over_pod=cfg.fsdp_over_pod,
                     seq_shard=seq_shard)
    if moe_pin != "auto" or moe_expert_axis != "model":
        import dataclasses
        plan = dataclasses.replace(
            plan, moe_pin=moe_pin, moe_expert_axis=moe_expert_axis
        )
    model = build_model(cfg)
    pshape = S.params_shape(model)

    t0 = time.perf_counter()  # monotonic: wall clock may jump mid-compile
    if shape.kind == "train":
        oc = AdamWConfig(state_dtype=cfg.opt_state_dtype)
        oshape = jax.eval_shape(lambda: opt.init_state(pshape, oc))
        state_shape = {"params": pshape, "opt": oshape}
        in_specs = S.train_input_specs(cfg, shape)
        state_shard = {
            "params": SR.make_param_shardings(mesh, pshape, cfg, plan),
            "opt": SR.make_opt_shardings(mesh, oshape, cfg, plan),
        }
        b_shard = SR.batch_sharding(mesh, plan, in_specs)
        fn = make_train_step(model, oc, microbatches=microbatches)
        jfn = jax.jit(fn, in_shardings=(state_shard, b_shard), donate_argnums=(0,))
        args = (state_shape, in_specs)
    elif shape.kind == "prefill":
        in_specs = S.prefill_input_specs(cfg, shape)
        p_shard = SR.make_param_shardings(mesh, pshape, cfg, plan)
        b_shard = SR.batch_sharding(mesh, plan, in_specs)

        def prefill(params, batch):
            return model.forward(params, batch, last_token_only=True)

        jfn = jax.jit(prefill, in_shardings=(p_shard, b_shard))
        args = (pshape, in_specs)
    else:  # decode
        tok_specs, cache_shape = S.decode_input_specs(model, cfg, shape)
        p_shard = SR.make_param_shardings(mesh, pshape, cfg, plan)
        c_shard = SR.cache_sharding(mesh, plan, cache_shape, cfg)
        t_shard = SR.batch_sharding(mesh, plan, tok_specs)
        if cfg.family == "encdec":
            def decode(params, cache, tokens):
                return model.decode_step(params, cache, tokens)
        else:
            decode = make_serve_step(model)
        jfn = jax.jit(
            decode,
            in_shardings=(p_shard, c_shard, t_shard["tokens"]),
            donate_argnums=(1,),
        )
        args = (pshape, cache_shape, tok_specs["tokens"])

    with mesh, use_plan(plan):
        lowered = jfn.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "output_bytes": getattr(ma, "output_size_in_bytes", 0),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
    }
    # per-device footprint ≈ args + temp (aliased outputs overlap args)
    mem["per_device_total"] = (
        mem["argument_bytes"] + mem["temp_bytes"]
    )
    print(f"memory_analysis: {ma}")
    cost = compiled.cost_analysis()
    cost = cost if isinstance(cost, dict) else (cost[0] if cost else {})
    print(f"cost_analysis: flops={cost.get('flops')} bytes={cost.get('bytes accessed')}")
    hlo = compiled.as_text()
    rep = build_report(
        arch, shape_name, mesh_name, chips, cost, hlo, mem, cfg, shape, shape.kind,
        device_kind=TARGET_DEVICE_KIND,
    )
    record.update(
        status="OK",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        hlo_bytes=len(hlo),
        roofline=rep.to_json(),
        fits_hbm_16g=bool(mem["per_device_total"] < 16e9),
    )
    return record


def cell_path(out_dir: str, arch: str, shape: str, mesh: str) -> str:
    return os.path.join(out_dir, f"{mesh}__{arch}__{shape}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--summarize", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    # §Perf variant knobs (experiments/perf/<tag>__<cell>.json)
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel activations over the model axis")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--param-dtype", default="",
                    help="override cfg.param_dtype (e.g. bfloat16)")
    ap.add_argument("--moe-groups", type=int, default=0,
                    help="GShard 2D dispatch groups (align with dp shards)")
    ap.add_argument("--moe-pin", default="auto",
                    choices=["auto", "group", "group_ep"],
                    help="MoE dispatch-buffer sharding pin")
    ap.add_argument("--moe-expert-axis", default="model",
                    choices=["model", "data"],
                    help="mesh axis sharding the expert dim of MoE weights")
    ap.add_argument("--remat", default="",
                    choices=["", "none", "block"],
                    help="override cfg.remat (quantify recompute waste)")
    ap.add_argument("--tag", default="", help="variant tag; files go to --out")
    args = ap.parse_args()
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)

    if args.summarize:
        summarize(out_dir)
        return

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        from repro.configs import ARCH_IDS
        from repro.models.config import SHAPES

        cells = [
            (a, s, m) for m in meshes for a in ARCH_IDS for s in SHAPES
        ]
        done = ok = failed = 0
        for a, s, m in cells:
            prefix = f"{args.tag}__" if args.tag else ""
            path = cell_path(out_dir, f"{prefix}{a}", s, m)
            if os.path.exists(path) and not args.force:
                done += 1
                continue
            print(f"=== {m} / {a} / {s} ===", flush=True)
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", a, "--shape", s, "--mesh", m, "--out", out_dir,
            ]
            # forward variant knobs to per-cell subprocesses
            if args.seq_shard:
                cmd.append("--seq-shard")
            if args.microbatches != 1:
                cmd += ["--microbatches", str(args.microbatches)]
            if args.param_dtype:
                cmd += ["--param-dtype", args.param_dtype]
            if args.moe_groups:
                cmd += ["--moe-groups", str(args.moe_groups)]
            if args.moe_pin != "auto":
                cmd += ["--moe-pin", args.moe_pin]
            if args.moe_expert_axis != "model":
                cmd += ["--moe-expert-axis", args.moe_expert_axis]
            if args.tag:
                cmd += ["--tag", args.tag]
            rc = subprocess.run(
                cmd,
                env={**os.environ, "PYTHONPATH": _pythonpath()},
                timeout=3600,
            )
            if rc.returncode == 0:
                ok += 1
            else:
                failed += 1
        print(f"done(existing)={done} ok={ok} failed={failed}")
        summarize(out_dir)
        return

    record = {"arch": args.arch, "shape": args.shape, "mesh": meshes[0]}
    try:
        record = run_cell(
            args.arch, args.shape, meshes[0], out_dir,
            seq_shard=args.seq_shard, microbatches=args.microbatches,
            param_dtype=args.param_dtype, moe_groups=args.moe_groups,
            moe_pin=args.moe_pin, moe_expert_axis=args.moe_expert_axis,
            remat=args.remat, tag=args.tag,
        )
    except Exception as e:
        record.update(status="FAIL", error=repr(e), traceback=traceback.format_exc())
        print(record["traceback"], file=sys.stderr)
    prefix = f"{args.tag}__" if args.tag else ""
    path = cell_path(out_dir, f"{prefix}{args.arch}", args.shape, meshes[0])
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({k: v for k, v in record.items() if k != "traceback"}, indent=1))
    sys.exit(0 if record.get("status") in ("OK", "SKIP") else 1)


def _pythonpath() -> str:
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    cur = os.environ.get("PYTHONPATH", "")
    return f"{src}:{cur}" if cur else src


def summarize(out_dir: str) -> None:
    rows = []
    for fn in sorted(os.listdir(out_dir)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(out_dir, fn)) as f:
            rows.append(json.load(f))
    print(f"{'mesh':6s} {'arch':22s} {'shape':12s} {'status':6s} "
          f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>10s} {'dom':>10s} "
          f"{'useful':>7s} {'mem/dev':>9s} {'compile':>8s}")
    for r in rows:
        rl = r.get("roofline") or {}
        mem_gb = (rl.get("memory_per_device_bytes", {}) or {}).get("per_device_total", 0) / 1e9
        print(
            f"{r.get('mesh',''):6s} {r.get('arch',''):22s} {r.get('shape',''):12s} "
            f"{r.get('status',''):6s} "
            f"{rl.get('compute_s', 0):10.4f} {rl.get('memory_s', 0):10.4f} "
            f"{rl.get('collective_s', 0):10.4f} {rl.get('dominant',''):>10s} "
            f"{rl.get('useful_ratio', 0):7.2f} {mem_gb:8.1f}G "
            f"{r.get('compile_s', 0):7.1f}s"
        )


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
