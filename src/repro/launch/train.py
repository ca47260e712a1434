"""Production training launcher: any assigned arch × shape on the
production mesh (dry-run lowering), or a reduced config trained for real on
this host's devices, always fed through the disaggregated data service.

Two modes:

  --execute      REDUCED config (``scaled_down()``), real training on this
                 host's default device — the chip on a TPU host, the CPU
                 elsewhere — with data from a local service deployment
                 (workers + dispatcher) through ``DeviceFeeder``.  Compiles
                 go to the persistent cache (``use_compile_cache``).  The
                 full-width served path on a chip is ``chip_smoke.py``.
  (default)      FULL config, production mesh: lower + compile the sharded
                 train_step exactly as the multi-pod dry-run does, on 512
                 CPU devices standing in for the pod, print the memory/cost
                 analysis, and exit — the pre-flight a real cluster launch
                 would run first.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --shape train_4k --seq-shard
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-2.7b --execute --steps 30
"""
import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--execute", action="store_true",
                    help="run a reduced config for real on this host")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    if args.execute:
        _execute_reduced(args)
        return

    # pre-flight: compile the production job (needs 512 host devices BEFORE
    # jax initializes, so re-exec through the dryrun module)
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", args.arch.replace("-", "_").replace(".", "p"),
        "--shape", args.shape, "--mesh", args.mesh,
        "--tag", "preflight",
    ]
    if args.seq_shard:
        cmd.append("--seq-shard")
    if args.microbatches != 1:
        cmd += ["--microbatches", str(args.microbatches)]
    import subprocess

    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    env = {**os.environ}
    env["PYTHONPATH"] = f"{src}:{env.get('PYTHONPATH', '')}"
    sys.exit(subprocess.run(cmd, env=env).returncode)


def _execute_reduced(args) -> None:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import start_service
    from repro.data import Dataset
    from repro.feed import DeviceFeeder
    from repro.launch import specs as S
    from repro.models import build_model
    from repro.models.config import ShapeConfig
    from repro.train import (
        AdamWConfig, init_train_state, make_train_step, save_checkpoint,
        use_compile_cache,
    )

    use_compile_cache()
    cfg = get_config(args.arch).scaled_down()
    model = build_model(cfg)
    opt = AdamWConfig(lr=1e-3, warmup_steps=5, decay_steps=args.steps)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    step_fn = jax.jit(make_train_step(model, opt, microbatches=args.microbatches))

    B, SEQ = 4, 64
    shape = ShapeConfig("exec", SEQ, B, "train")
    spec = S.train_input_specs(cfg, shape)

    def make_batch(i):
        rng = np.random.default_rng(int(i))
        out = {}
        for k, v in spec.items():
            shp = v.shape[1:]  # per-example
            if jnp.issubdtype(v.dtype, jnp.integer):
                out[k] = rng.integers(1, cfg.vocab_size, shp).astype(np.int32)
            else:
                out[k] = rng.standard_normal(shp).astype(np.float32)
        return out

    svc = start_service(num_workers=args.workers)
    try:
        ds = (
            Dataset.range(10_000)
            .map(make_batch)
            .batch(B, drop_remainder=True)
            .distribute(service=svc, processing_mode="dynamic")
        )
        # device feed: background fetch + host->device transfer with a
        # double buffer — the step function never waits on the host loop
        # unless the service itself falls behind (feeder.metrics says which)
        with DeviceFeeder(ds, depth=2) as feeder:
            t0 = time.perf_counter()
            for step in range(1, args.steps + 1):
                batch = feeder.next()
                state, metrics = step_fn(state, batch)
                if step % 5 == 0 or step == args.steps:
                    jax.block_until_ready(metrics["loss"])
                    print(f"[{args.arch}] step {step:3d} "
                          f"loss {float(metrics['loss']):.4f} "
                          f"({(time.perf_counter()-t0)/step:.2f}s/step)",
                          flush=True)
            fm = feeder.metrics
            bd = fm.breakdown()
            print(f"[{args.arch}] feed: idle {fm.idle_s_per_step*1e3:.1f}ms/step "
                  f"(stall {fm.stall_fraction:.1%}) — "
                  f"fetch {bd['fetch']:.0%} / transfer {bd['transfer']:.0%} / "
                  f"compute {bd['compute']:.0%}", flush=True)
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, args.steps, state)
            print(f"checkpoint -> {args.ckpt_dir}")
    finally:
        svc.orchestrator.stop()


if __name__ == "__main__":
    main()
