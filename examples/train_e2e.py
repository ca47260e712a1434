"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
ALL input preprocessing disaggregated to service workers (the paper's
architecture at laptop scale).

Pipeline (on workers): ``repro.data.pipelines.lm_pipeline`` — synthetic
Zipf documents packed into seq_len-token sequences -> batch.  Trainer
(this process): jitted train_step, checkpoint every 50 steps, resumable
after crash via --resume.  Compiles go to the persistent cache
(``repro.train.use_compile_cache``).

Run:   PYTHONPATH=src python examples/train_e2e.py --steps 200
Quick: PYTHONPATH=src python examples/train_e2e.py --steps 20 --tiny
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import start_service
from repro.data.pipelines import lm_pipeline
from repro.feed import DeviceFeeder
from repro.models import build_model
from repro.train import (
    AdamWConfig,
    init_train_state,
    latest_step,
    make_train_step,
    restore_checkpoint,
    save_checkpoint,
    use_compile_cache,
)

SEQ = 256
BATCH = 8


def build(tiny: bool):
    cfg = get_config("starcoder2-3b")
    if tiny:
        cfg = cfg.scaled_down()
    else:
        # ~100M-param config of the same family
        cfg = cfg.replace(
            num_layers=10, d_model=640, num_heads=10, num_kv_heads=2,
            head_dim=64, d_ff=2560, vocab_size=32768,
            dtype="float32", param_dtype="float32", remat="none",
        )
    return cfg, build_model(cfg)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_e2e")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    use_compile_cache()
    cfg, model = build(args.tiny)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        )
    )
    print(f"model: {cfg.name} reduced, {n_params/1e6:.1f}M params")

    opt = AdamWConfig(lr=3e-4, warmup_steps=20, decay_steps=args.steps)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    start = 0
    if args.resume and latest_step(args.ckpt_dir) is not None:
        state, start = restore_checkpoint(args.ckpt_dir, state)
        print(f"resumed from step {start}")
    step_fn = jax.jit(make_train_step(model, opt), donate_argnums=(0,))

    service = start_service(num_workers=args.workers)
    try:
        ds = lm_pipeline(cfg.vocab_size, SEQ, BATCH).distribute(
            service=service, processing_mode="dynamic"
        )
        # the feeder replaces the manual next(it) + jnp.asarray loop:
        # fetch and host->device transfer run behind a double buffer, so
        # the only time the step waits is when the SERVICE falls behind —
        # visible as feeder.metrics.idle_s, not hidden in the step time
        with DeviceFeeder(ds, depth=2) as feeder:
            t0 = time.perf_counter()
            tokens_seen = 0
            for step in range(start + 1, args.steps + 1):
                batch = feeder.next()
                state, metrics = step_fn(state, batch)
                tokens_seen += BATCH * SEQ
                if step % 10 == 0 or step == args.steps:
                    jax.block_until_ready(metrics["loss"])
                    tps = tokens_seen / (time.perf_counter() - t0)
                    fm = feeder.metrics
                    print(
                        f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                        f"lr {float(metrics['lr']):.2e}  "
                        f"idle {fm.idle_s_per_step*1e3:.1f}ms/step  "
                        f"{tps:,.0f} tok/s"
                    )
                if step % args.ckpt_every == 0:
                    save_checkpoint(args.ckpt_dir, step, state)
                    print(f"  checkpoint @ {step}")
            bd = feeder.metrics.breakdown()
            print(f"feed breakdown: fetch {bd['fetch']:.0%} / "
                  f"transfer {bd['transfer']:.0%} / compute {bd['compute']:.0%}")
    finally:
        service.orchestrator.stop()
    print("done — re-run with --resume to continue from the last checkpoint")


if __name__ == "__main__":
    main()
