"""Chip smoke: the served training path on a TPU, run and checked end to end.

One process owns the chip and drives the system through the entry points a
user calls:

* ``kernels`` — each Pallas kernel, compiled by Mosaic (not interpreted),
  runs once at real widths against its ``ref.py`` run on the host's CPU,
  so the tolerance is the one the kernel's CPU test uses: the attention
  and scan kernels with f32 matmuls, the selection and counting matmuls of
  ``fused_augment`` and ``moe_router`` at the default precision.
  (XLA's own f32 on the TPU is not that reference: at these widths the
  ``ssd_scan`` ref on the chip is 5.6e-3 off the CPU's.)
* ``served`` — ``start_service(2 workers, tcp, worker_processes=2)`` runs
  ``lm_pipeline`` (packed Zipf documents, 4096 tokens per sequence) in
  forked pool children; the co-located client negotiates the ``shm://``
  ring; ``DeviceFeeder(depth=2)`` places each batch on the chip; a jitted
  full-width StarCoder2-3B train step (depth cut to 4 layers, batch 1×4096,
  bf16 compute, fp32 params, block remat, AdamW with donated state) takes
  the batches.  Checks: the loss is finite and falls; every batch that
  reached the device, read back after later batches went through the ring,
  matches one sequence of the local reference (the same graph through
  ``Dataset.iterator()``) exactly once; the ring served batches; the pool
  never fell back to running a pipeline in the worker's thread.
* ``sharded`` (``--chips 4`` only, and then alone) — the feeder places a
  4×4096 global batch on a ("data", "model") = (4, 1) mesh with params
  sharded by ``repro.dist.sharding_rules``; the gradients of the first
  batch are also taken on one chip through ``make_grad_fn(...,
  microbatches=4)`` from the same params, and the loss and every gradient
  leaf must agree.  The same comparison must catch a planted fault: the
  first batch with one row dropped from the loss on the mesh.  Depth is cut
  to 2 layers here: the 4-layer one-chip microbatched reference needs
  18.12G of the v5e's 15.75G HBM.

Run from the checkout root:  python chip_smoke.py [--chips 4]

Without a TPU it exits non-zero before any phase.  Every phase runs; any
failed check or phase exits non-zero.  On success the last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import hashlib
import json
import math
import multiprocessing
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Callable, Counter, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import start_service  # noqa: E402
from repro.data.pipelines import lm_pipeline  # noqa: E402
from repro.feed import DeviceFeeder  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.train import (  # noqa: E402
    AdamWConfig,
    init_train_state,
    make_grad_fn,
    make_train_step,
    use_compile_cache,
)

ARCH = "starcoder2-3b"
SEQ = 4096
LAYERS = 4  # of 30: the 1×4096 step fits one v5e's HBM at full width
SHARDED_LAYERS = 2  # the 4-layer one-chip microbatched reference does not
WORKERS = 2
WORKER_PROCESSES = 2  # pool children per worker
WARMUP_STEPS = 4
TIMED_STEPS = 8
# mesh vs one-chip microbatched, on the loss and on every gradient leaf:
# the same bf16 math, partitioned and summed in another order.  Readings on
# four v5e chips (PERF.md): agreeing leaves differ by at most 2.3e-3, the
# first batch with one row dropped moves every leaf by 3.0e-2 to 7.9e-2;
# the bound sits between them.
SHARDED_RTOL = 1e-2
FALLBACKS = "executor_inthread_fallbacks_total"


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def row_digests(batch: Dict[str, Any]) -> List[str]:
    """One digest per sequence (row) of a host batch."""
    tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
    return [
        hashlib.sha1(tokens[r].tobytes() + labels[r].tobytes()).hexdigest()
        for r in range(tokens.shape[0])
    ]


def reference_rows(pipeline: Any) -> Counter[str]:
    """The local reference: the same graph through ``Dataset.iterator()``."""
    return collections.Counter(d for b in pipeline.iterator() for d in row_digests(b))


def pool_fallbacks(orchestrator: Any) -> int:
    """In-thread fallbacks of every worker's executor pool."""
    return int(sum(w.registry.values().get(FALLBACKS, 0) for w in orchestrator.workers))


def pool_children() -> int:
    return sum(p.name.startswith("repro-exec") for p in multiprocessing.active_children())


def device_put_reads_after_return(device: Any) -> bool:
    """Whether ``device_put`` returns before its copy has read the host
    buffer: overwrite 64 MiB of source right after the call and look for
    the new bytes on the device.  (The feeder waits for its copies either
    way; this says whether it has to.)"""
    host = np.zeros(64 << 20, np.uint8)
    placed = jax.device_put(host, device)
    host[:] = 1
    return bool(np.asarray(placed).any())


def peak_bytes(device: Any) -> Optional[int]:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# served: service -> shm ring -> DeviceFeeder -> train step on one chip
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ServedRun:
    losses: List[float]
    device_rows: Counter[str]
    reference: Counter[str]
    extra_batch: bool
    shm_batches: int
    fallbacks: int
    children: int
    compile_s: float
    steps_per_s: float
    idle_ms_per_step: float
    split: Dict[str, float]  # fetch / transfer / compute shares of the timed window
    peak_bytes: Optional[int]


def run_served(
    cfg: ModelConfig,
    *,
    seq_len: int,
    batch_size: int,
    warmup: int,
    steps: int,
    seed: int = 0,
) -> ServedRun:
    n_batches = warmup + steps
    pipeline = lm_pipeline(
        cfg.vocab_size, seq_len, batch_size,
        num_sequences=n_batches * batch_size, shuffle_buffer=64, seed=seed,
    )
    reference = reference_rows(pipeline)

    model = build_model(cfg)
    opt = AdamWConfig(warmup_steps=1, decay_steps=n_batches)
    state = jax.jit(lambda key: init_train_state(model, key, opt))(
        jax.random.PRNGKey(seed)
    )
    spec = {
        k: jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)
        for k in ("tokens", "labels")
    }
    t0 = time.perf_counter()
    step = (
        jax.jit(make_train_step(model, opt), donate_argnums=(0,))
        .lower(state, spec)
        .compile()
    )
    compile_s = time.perf_counter() - t0

    svc = start_service(
        num_workers=WORKERS, transport="tcp", worker_processes=WORKER_PROCESSES
    )
    try:
        dds = pipeline.distribute(service=svc, processing_mode="dynamic")
        placed, losses = [], []
        with DeviceFeeder(dds, depth=2) as feeder:
            fm = feeder.metrics
            for i in range(n_batches):
                if i == warmup:
                    jax.block_until_ready(state)
                    mark = (fm.steps, fm.idle_s, fm.fetch_s, fm.transfer_s, fm.compute_s)
                    t0 = time.perf_counter()
                batch = feeder.next(timeout=600)
                state, metrics = step(state, batch)
                placed.append(batch)
                losses.append(metrics["loss"])
            jax.block_until_ready(state)
            timed_s = time.perf_counter() - t0
            # the compute time of the last step is booked at the next call
            try:
                feeder.next(timeout=120)
                extra_batch = True
            except StopIteration:
                extra_batch = False
            n = fm.steps - mark[0]
            idle, fetch, transfer, compute = (
                cur - prev
                for cur, prev in zip(
                    (fm.idle_s, fm.fetch_s, fm.transfer_s, fm.compute_s), mark[1:]
                )
            )
        children = pool_children()
        fallbacks = pool_fallbacks(svc.orchestrator)
        shm_batches = int(dds.last_client.metrics.shm_batches)
    finally:
        svc.orchestrator.stop()

    # read back only now, after every later batch has passed through the ring
    device_rows = collections.Counter(
        d for b in jax.device_get(placed) for d in row_digests(b)
    )
    total = fetch + transfer + compute
    return ServedRun(
        losses=[float(x) for x in jax.device_get(losses)],
        device_rows=device_rows,
        reference=reference,
        extra_batch=extra_batch,
        shm_batches=shm_batches,
        fallbacks=fallbacks,
        children=children,
        compile_s=compile_s,
        steps_per_s=steps / timed_s,
        idle_ms_per_step=1e3 * idle / max(1, n),
        split={
            "fetch": fetch / total if total else 0.0,
            "transfer": transfer / total if total else 0.0,
            "compute": compute / total if total else 0.0,
        },
        peak_bytes=peak_bytes(jax.devices()[0]),
    )


def check_served(run: ServedRun) -> None:
    check(all(math.isfinite(x) for x in run.losses), f"non-finite loss: {run.losses}")
    check(
        run.losses[-1] < run.losses[0],
        f"loss did not fall: first {run.losses[0]!r}, last {run.losses[-1]!r}",
    )
    missing = run.reference - run.device_rows
    extra = run.device_rows - run.reference
    check(
        not missing and not extra,
        f"device batches differ from the local reference: {sum(missing.values())} "
        f"missing, {sum(extra.values())} unexpected or repeated",
    )
    check(not run.extra_batch, "the feeder delivered more batches than the data holds")
    check(run.shm_batches > 0, "no batch came through the shm ring")
    check(run.children > 0, "no executor pool child ran")
    check(run.fallbacks == 0, f"{run.fallbacks} pipelines fell back to the worker thread")


def served_phase() -> None:
    cfg = get_config(ARCH).replace(num_layers=LAYERS)
    log(
        f"served: {ARCH} full width (d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}), dtype {cfg.dtype}, params {cfg.param_dtype}, "
        f"remat {cfg.remat}"
    )
    log(
        f"served: cuts: layers {LAYERS} of {get_config(ARCH).num_layers}; "
        f"batch 1x{SEQ} tokens; {WARMUP_STEPS} warm-up + {TIMED_STEPS} timed steps"
    )
    log(
        f"served: service tcp, {WORKERS} workers, worker_processes="
        f"{WORKER_PROCESSES}; DeviceFeeder depth 2"
    )
    run = run_served(
        cfg, seq_len=SEQ, batch_size=1, warmup=WARMUP_STEPS, steps=TIMED_STEPS
    )
    log(f"served: compile_s {run.compile_s!r}")
    log(f"served: losses {run.losses}")
    log(f"served: steps_per_s {run.steps_per_s!r}")
    log(
        f"served: feeder idle_ms_per_step {run.idle_ms_per_step!r}; split fetch "
        f"{run.split['fetch']!r} transfer {run.split['transfer']!r} compute "
        f"{run.split['compute']!r}"
    )
    log(
        f"served: shm_batches {run.shm_batches}; pool children {run.children}; "
        f"in-thread fallbacks {run.fallbacks}"
    )
    log(
        f"served: device rows {sum(run.device_rows.values())} vs reference "
        f"{sum(run.reference.values())} (distinct {len(run.reference)})"
    )
    log(f"served: peak_bytes_in_use {run.peak_bytes}")
    log(
        "served: device_put read the host buffer after returning: "
        f"{device_put_reads_after_return(jax.devices()[0])}"
    )
    check_served(run)
    log("served: OK")


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel on the chip against its ref.py
# ---------------------------------------------------------------------------
def kernels_phase() -> None:
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.kernels.fused_augment.ops import fused_augment
    from repro.kernels.fused_augment.ref import fused_augment_ref
    from repro.kernels.moe_router.ops import moe_router
    from repro.kernels.moe_router.ref import moe_router_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    rng = np.random.default_rng(0)
    host = jax.devices("cpu")[0]

    def randn(*shape, scale=1.0):
        return rng.standard_normal(shape).astype(np.float32) * scale

    def on_host(ref: Callable[..., Any], *args: Any, **kw: Any) -> Any:
        """The reference, in f32 on the host's CPU."""
        args = [jax.device_put(a, host) if isinstance(a, np.ndarray) else a for a in args]
        with jax.default_device(host):
            return jax.device_get(ref(*args, **kw))

    def max_err(a, b) -> float:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)))

    failed = []

    def report(name: str, err: float, tol: float) -> None:
        ok = err <= tol
        log(f"kernels: {name} max_abs_err {err!r} tol {tol} {'OK' if ok else 'FAIL'}")
        if not ok:
            failed.append(name)

    # f32 matmuls in the attention and scan kernels too, as the CPU tests'
    # interpret mode has
    with jax.default_matmul_precision("highest"):
        q, k, v = randn(1, SEQ, 24, 128), randn(1, SEQ, 2, 128), randn(1, SEQ, 2, 128)
        report(
            "flash_attention",
            max_err(
                flash_attention(q, k, v, causal=True, window=SEQ),
                on_host(flash_attention_ref, q, k, v, causal=True, window=SEQ),
            ),
            2e-5,
        )
        del q, k, v

        S = 32768
        q, k, v = randn(8, 24, 128), randn(8, S, 2, 128), randn(8, S, 2, 128)
        lens = rng.integers(1, S + 1, 8).astype(np.int32)
        report(
            "decode_attention",
            max_err(
                decode_attention(q, k, v, lens),
                on_host(decode_attention_ref, q, k, v, lens),
            ),
            2e-5,
        )
        del q, k, v

        B, L, H, P, N = 1, SEQ, 80, 64, 128
        args = (
            randn(B, L, H, P, scale=0.5),
            np.abs(randn(B, L, H, scale=0.1)),
            -np.abs(randn(H)),
            randn(B, L, H, N, scale=0.3),
            randn(B, L, H, N, scale=0.3),
            randn(H),
        )
        report("ssd_scan", max_err(ssd_scan(*args), on_host(ssd_scan_ref, *args)), 5e-4)
        del args

    # the default (single-pass) precision: these selection and counting
    # matmuls have operands exact in bf16
    n, hw, crop = 128, 256, 224
    images = rng.integers(0, 256, (n, hw, hw, 3)).astype(np.uint8)
    crops = rng.integers(0, hw - crop + 1, (n, 2)).astype(np.int32)
    flips = rng.integers(0, 2, n).astype(np.int32)
    mean = np.asarray([0.485, 0.456, 0.406], np.float32)
    std = np.asarray([0.229, 0.224, 0.225], np.float32)
    report(
        "fused_augment",
        max_err(
            fused_augment(images, crops, flips, mean, std, out_h=crop, out_w=crop),
            on_host(fused_augment_ref, images, crops, flips, mean, std, crop, crop),
        ),
        1e-5,
    )

    T, E, topk = 4096, 64, 6
    logits = randn(T, E)
    ids, gates, slots = jax.device_get(moe_router(logits, k=topk, capacity=T))
    want_ids, want_gates, want_slots = on_host(moe_router_ref, logits, topk, T)
    mismatched = int(np.sum(ids != want_ids) + np.sum(slots != want_slots))
    log(f"kernels: moe_router id/slot mismatches {mismatched}")
    if mismatched:
        failed.append("moe_router")
    report("moe_router gates", max_err(gates, want_gates), 1e-6)

    check(not failed, f"kernels off their reference: {failed}")
    log("kernels: OK")


# ---------------------------------------------------------------------------
# sharded (--chips 4): the feeder on a data-parallel mesh vs one chip
# ---------------------------------------------------------------------------
def leaf_errors(got: Any, want: Any) -> Dict[str, float]:
    """Per parameter leaf: ||got - want|| / ||want||, in f64 on the host."""
    out = {}
    for (path, w), g in zip(
        jax.tree_util.tree_flatten_with_path(want)[0], jax.tree.leaves(got)
    ):
        w = np.asarray(w, np.float64)
        diff = np.linalg.norm(np.asarray(g, np.float64) - w)
        out[jax.tree_util.keystr(path)] = float(diff / max(np.linalg.norm(w), 1e-30))
    return out


def tree_norm(tree: Any) -> float:
    return math.sqrt(
        sum(float(np.sum(np.square(np.asarray(x, np.float64)))) for x in jax.tree.leaves(tree))
    )


@dataclasses.dataclass
class ShardedRun:
    loss: Dict[str, float]  # first batch: "mesh" and "one_chip" (microbatched)
    grad_norm: Dict[str, float]
    leaf_err: Dict[str, float]  # per leaf, mesh grads vs one-chip grads
    planted_err: Dict[str, float]  # the same with one row dropped on the mesh
    losses: List[float]
    rows_per_device: List[List[int]]  # per batch: rows held by each device
    device_rows: Counter[str]
    reference: Counter[str]
    distinct_rows: bool


def run_sharded(
    cfg: ModelConfig,
    *,
    devices: List[Any],
    seq_len: int,
    steps: int,
    seed: int = 0,
) -> ShardedRun:
    from repro.dist import sharding_rules as SR
    from repro.dist.context import use_plan
    from repro.launch.mesh import make_mesh, make_plan
    from repro.train.step import PAD_ID

    n_dev = len(devices)
    mesh = make_mesh((n_dev, 1), ("data", "model"), devices=devices)
    plan = make_plan(mesh)
    # DYNAMIC shards batch separately and drop their remainders, so the
    # data holds more rows than the steps take: rows are checked to be
    # reference rows, each at most once
    pipeline = lm_pipeline(
        cfg.vocab_size, seq_len, n_dev,
        num_sequences=4 * steps * n_dev, shuffle_buffer=64, seed=seed,
    )
    reference = reference_rows(pipeline)
    model = build_model(cfg)
    opt = AdamWConfig(warmup_steps=1, decay_steps=steps)

    def init(key):
        return init_train_state(model, key, opt)

    key = jax.random.PRNGKey(seed)
    spec = {
        k: jax.ShapeDtypeStruct((n_dev, seq_len), jnp.int32)
        for k in ("tokens", "labels")
    }
    state_shape = jax.eval_shape(init, key)
    param_shard = SR.make_param_shardings(mesh, state_shape["params"], cfg, plan)
    state_shard = {
        "params": param_shard,
        "opt": SR.make_opt_shardings(mesh, state_shape["opt"], cfg, plan),
    }
    batch_shard = SR.batch_sharding(mesh, plan, spec)

    svc = start_service(
        num_workers=WORKERS, transport="tcp", worker_processes=WORKER_PROCESSES
    )
    try:
        dds = pipeline.distribute(service=svc, processing_mode="dynamic")
        with DeviceFeeder(dds, mesh=mesh, plan=plan, depth=2) as feeder:
            batches = [feeder.next(timeout=600) for _ in range(steps)]
    finally:
        svc.orchestrator.stop()

    rows_per_device, distinct = [], True
    for b in batches:
        shards = b["tokens"].addressable_shards
        per_dev = {s.device.id: s.data.shape[0] for s in shards}
        rows_per_device.append([per_dev.get(d.id, 0) for d in devices])
        digests = [hashlib.sha1(np.asarray(s.data).tobytes()).hexdigest() for s in shards]
        distinct &= len(set(digests)) == len(digests) == n_dev
    first = jax.device_get(batches[0])

    with mesh, use_plan(plan):
        state = jax.jit(init, out_shardings=state_shard)(key)

    # one chip, the same params: the first batch as microbatches of one row
    one_grads = jax.jit(make_grad_fn(model, microbatches=n_dev))
    params = jax.device_put(state["params"], devices[0])
    loss_one, _, grads_one = jax.device_get(
        one_grads(params, jax.device_put(first, devices[0]))
    )
    del params

    with mesh, use_plan(plan):
        mesh_grads = jax.jit(
            make_grad_fn(model),
            in_shardings=(param_shard, batch_shard),
            out_shardings=(None, None, param_shard),
        )
        loss_mesh, _, grads_mesh = jax.device_get(mesh_grads(state["params"], batches[0]))
        # a planted fault the comparison must catch: the last row is dropped
        # from the loss (all its labels are padding) on the mesh
        dropped = dict(first, labels=first["labels"].copy())
        dropped["labels"][-1] = PAD_ID
        _, _, grads_planted = jax.device_get(
            mesh_grads(state["params"], jax.device_put(dropped, batch_shard))
        )

        step = jax.jit(
            make_train_step(model, opt),
            in_shardings=(state_shard, batch_shard),
            out_shardings=(state_shard, None),
            donate_argnums=(0,),
        )
        losses = []
        for b in batches:
            state, m = step(state, b)
            losses.append(m["total_loss"])
        losses = [float(x) for x in jax.device_get(losses)]

    device_rows = collections.Counter(
        d for b in jax.device_get(batches) for d in row_digests(b)
    )
    return ShardedRun(
        loss={"mesh": float(loss_mesh), "one_chip": float(loss_one)},
        grad_norm={"mesh": tree_norm(grads_mesh), "one_chip": tree_norm(grads_one)},
        leaf_err=leaf_errors(grads_mesh, grads_one),
        planted_err=leaf_errors(grads_planted, grads_one),
        losses=losses,
        rows_per_device=rows_per_device,
        device_rows=device_rows,
        reference=reference,
        distinct_rows=distinct,
    )


def check_sharded(run: ShardedRun, rtol: float = SHARDED_RTOL) -> None:
    check(all(math.isfinite(x) for x in run.losses), f"non-finite loss: {run.losses}")
    a, b = run.loss["mesh"], run.loss["one_chip"]
    check(abs(a - b) <= rtol * abs(b), f"loss: mesh {a!r} vs one chip {b!r}")
    worst = max(run.leaf_err, key=run.leaf_err.get)
    check(
        run.leaf_err[worst] <= rtol,
        f"gradient leaf {worst}: mesh vs one chip relative error "
        f"{run.leaf_err[worst]!r} > {rtol}",
    )
    check(
        max(run.planted_err.values()) > rtol,
        "the gradient comparison did not catch a dropped batch row",
    )
    check(
        all(rows == [1] * len(rows) for rows in run.rows_per_device),
        f"batch rows not one per device: {run.rows_per_device}",
    )
    check(run.distinct_rows, "two devices hold the same batch row")
    check(
        not run.device_rows - run.reference,
        "a device row is not in the local reference, or came twice",
    )


def sharded_phase() -> None:
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX has {len(jax.devices())}")
    cfg = get_config(ARCH).replace(num_layers=SHARDED_LAYERS)
    log(
        f"sharded: {ARCH} full width, cuts: layers {SHARDED_LAYERS} of "
        f"{get_config(ARCH).num_layers}; global batch 4x{SEQ}; mesh (data, model) = (4, 1)"
    )
    run = run_sharded(cfg, devices=devices, seq_len=SEQ, steps=3)
    log(f"sharded: first batch loss {run.loss}; grad_norm {run.grad_norm}")
    for name, err in run.leaf_err.items():
        log(
            f"sharded: grad {name} mesh vs one chip {err!r}; "
            f"dropped row {run.planted_err[name]!r}"
        )
    log(
        f"sharded: worst leaf {max(run.leaf_err.values())!r}, bound {SHARDED_RTOL}, "
        f"dropped row min {min(run.planted_err.values())!r} "
        f"max {max(run.planted_err.values())!r}"
    )
    log(f"sharded: losses {run.losses}")
    log(f"sharded: rows per device per batch {run.rows_per_device}")
    check_sharded(run)
    log("sharded: OK")


# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the data-parallel feeder phase on four chips",
    )
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU: JAX found {len(devices)} {dev.platform} "
            f"device(s) ({dev.device_kind})",
            file=sys.stderr,
        )
        return 2
    cache = use_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"compile cache: {cache}")
    shm = shutil.disk_usage("/dev/shm")
    log(f"/dev/shm: {shm.total} bytes, {shm.free} free")

    phases: Dict[str, Callable[[], None]] = (
        {"sharded": sharded_phase}
        if args.chips == 4
        else {"kernels": kernels_phase, "served": served_phase}
    )
    failed = []
    for name, phase in phases.items():
        try:
            phase()
        except Exception as e:  # record, run the next phase, exit non-zero
            traceback.print_exc()
            log(f"{name}: FAILED: {e!r}")
            failed.append(name)
    if failed:
        log(f"chip_smoke: failed phases: {failed}")
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
