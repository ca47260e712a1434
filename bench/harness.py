"""One benchmark run of one cell: set-up, warm-up, timed window, checks.

The timed path is the one a user of the system drives:
``Dataset.distribute(service=…, processing_mode="dynamic")`` →
``DeviceFeeder.next()`` → ``jax.jit(make_train_step(...), donate_argnums=0)``.

Set-up builds the weights on the device from the seed (``bench.models``),
compiles the cell's step (through the program's persistent compilation
cache), starts the service and the feeder, and drives the step through its
first three steps — the steps the reference follows — then warms up until
the feeder's queue is full.  The window runs the loop a training job runs:
fetch, dispatch step n, wait for the loss of step n−1, so one step stays
queued on the device.  Once the window has closed and the program's state
is freed, the checks compare what reached the device with the traffic's
reference and the first three steps with the model's plain float32
reference (``bench.refops``).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import random
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
OUT = BENCH / "out"
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import start_service  # noqa: E402
from repro.feed import DeviceFeeder  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.train import AdamWConfig, init_state, make_train_step, use_compile_cache  # noqa: E402

from . import program as P  # noqa: E402
from . import refops as R  # noqa: E402
from . import stats, traffic  # noqa: E402
from . import trace as T  # noqa: E402

PROOF_STEPS = 3  # the steps the reference follows
SAMPLED_BATCHES = 4  # batches whose every leaf is compared (all rows' identities are)
NEXT_TIMEOUT_S = 120.0
QUEUE_FILL_TIMEOUT_S = 60.0
IDENTITY_LEAVES = ("tokens", "labels")
MODEL_NUMBERS = ("loss_gap", "grad1_gap", "grad1_median_gap", "grad1_p75_gap", "change_gap")


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path


def _json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, its configuration, mix and
    limits, and the metrics it reports."""
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    mix = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _json(root / "bench" / "limits" / f"{name}.json")

    def ours(metric: Dict[str, Any]) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if ours(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if ours(m) and m["moves"] in reported]
    return Cell(name, int(w["chips"]), config, mix, limits, e2e, layer, root)


def load_reader(root: Path, metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_module(config: Dict[str, Any]):
    return importlib.import_module(f"bench.models.{config['model_module']}")


def peaks(kind: str) -> Dict[str, float]:
    table = _json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


# ---------------------------------------------------------------------------
# Counters of the layers, read at the window's edges
# ---------------------------------------------------------------------------
def worker_counters(orchestrator: Any) -> Tuple[float, int]:
    """CPU seconds of every pipeline op on every worker (pool children
    included) and the batches the pipelines produced: the elements of each
    pipeline's last op."""
    cpu, batches = 0.0, 0
    for w in orchestrator.workers:
        for task in w.rpc_metrics_dump()["tasks"].values():
            rows = task["profile"]
            cpu += sum(r["cpu_s"] for r in rows)
            if rows:
                batches += max(rows, key=lambda r: r["index"])["elements"]
    return cpu, batches


def counters(orchestrator: Any, client: Any, feeder: Any) -> Dict[str, Any]:
    """The layers' counters: the worker ops' CPU time, the client's and the
    feeder's metrics, and every counter of the program's registries
    (``bench.program.counters``)."""
    cpu, produced = worker_counters(orchestrator)
    cm, fm = client.metrics, feeder.metrics
    return {
        **P.counters(orchestrator),
        "worker_cpu_s": cpu,
        "worker_batches": produced,
        "client_fetch_s": float(cm.fetch_time),
        "client_batches": float(cm.batches),
        "client_shm_batches": float(cm.shm_batches),
        "feed_transfer_s": fm.transfer_s,
        "feed_steps": fm.steps,
    }


def window_delta(before: Dict[str, Any], after: Dict[str, Any]) -> Dict[str, Any]:
    """Each counter's change over the window; one that first appeared in
    the window counts from 0.  A counter the program lacks reads None."""
    delta: Dict[str, Any] = collections.defaultdict(lambda: None)
    for k, v in after.items():
        b = before.get(k, 0.0)
        delta[k] = None if v is None or b is None else v - b
    return delta


def step_metrics(mets: List[Dict[str, Any]]) -> Dict[str, float]:
    """The mean over the window of every scalar of the step's metrics."""
    mets = jax.device_get(mets)
    keys = [k for k in (mets[0] if mets else {}) if np.ndim(mets[0][k]) == 0]
    return {k: float(np.mean([np.asarray(m[k], np.float64) for m in mets])) for k in keys}


def recording(step: Callable, into: List[Any]) -> Callable:
    """``step`` that also keeps each call's metrics (traced runs only)."""
    def call(state, batch):
        state, met = step(state, batch)
        into.append(met)
        return state, met

    return call


# ---------------------------------------------------------------------------
# The plain reference and the comparison
# ---------------------------------------------------------------------------
def leaf_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per leaf: |norm(got) − norm(want)| over the larger of the leaf's
    reference norm and the median leaf's."""
    return np.abs(got - want) / np.maximum(want, float(np.median(want)))


def reference_readings(config: Dict[str, Any], seed: int, batches: List[Dict[str, np.ndarray]],
                       precision: str = "f32", devices: Optional[List[Any]] = None) -> Dict[str, Any]:
    """The model's reference through the first steps on ``batches``: the
    loss of each step, the norm of every leaf of Adam's first moment after
    step 1, and the parameters after the last step.

    The gradient is computed on the first device, summed over blocks of
    ``reference_rows`` rows where the configuration gives them; Adam's state
    lives on the device that ``reference_state_device`` names where there is
    one, so that a four-row reference of the full width fits a chip."""
    mod, m = model_module(config), config
    opt = config["optimizer"]
    ein = R.make_einsum(precision)
    devices = devices or jax.devices()[:1]
    dev = devices[0]
    sdev = devices[min(config.get("reference_state_device", 0), len(devices) - 1)]
    key = R.seed_key(seed)

    def block_grads(params, batch, count):
        def part(p):
            sums = mod.loss_sums(m, p, batch, ein)
            return (sums["nll"] + sums["z"]) / count, sums["nll"]

        (_, nll), g = jax.value_and_grad(part, has_aux=True)(params)
        return nll, g

    grad_fn = jax.jit(block_grads)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    update = jax.jit(lambda p, g, s: R.adamw_update(opt, p, g, s), donate_argnums=(0, 1, 2))
    with jax.default_device(dev):
        params = jax.jit(lambda k: mod.init_params(m, k))(key)
    with jax.default_device(sdev):
        state = jax.jit(R.adamw_init)(jax.device_put(params, sdev))
    losses, m1 = [], None
    for i, b in enumerate(batches):
        rows = b["labels"].shape[0]
        blk = config.get("reference_rows") or rows
        count = np.float32(max(int(np.sum(b["labels"] != R.PAD_ID)), 1))
        g, nll = None, 0.0
        for r in range(0, rows, blk):
            part = jax.device_put({k: v[r : r + blk] for k, v in b.items()}, dev)
            n_b, g_b = grad_fn(params, part, count)
            g = g_b if g is None else add(g, g_b)
            nll += float(n_b)
        params, state = update(jax.device_put(params, sdev), jax.device_put(g, sdev), state)
        params = jax.device_put(params, dev)
        del g
        losses.append(nll / float(count))
        if i == 0:
            m1 = np.asarray(jax.jit(R.leaf_norms)(state["m"]), np.float64)
    del state
    return {"losses": losses, "m1": m1, "params": params}


def change_norms(config: Dict[str, Any], seed: int, params: Any, device: Any = None) -> np.ndarray:
    """Norm of every leaf of ``params`` minus the seed's initial weights."""
    mod, m = model_module(config), config
    device = device or jax.devices()[0]
    with jax.default_device(device):
        p0 = jax.jit(lambda k: mod.init_params(m, k))(R.seed_key(seed))
        diff = jax.jit(lambda a, b: R.leaf_norms(jax.tree.map(jnp.subtract, a, b)))
        return np.asarray(diff(jax.device_put(params, device), p0), np.float64)


def compare(got: Dict[str, Any], ref: Dict[str, Any], ref_change: np.ndarray,
            got_change: np.ndarray, names: List[str]) -> Dict[str, Any]:
    """The model's numbers: the worst relative gap of a step's loss; of a
    leaf's first-moment norm after step 1 (the worst leaf, the median leaf,
    and the upper quartile of the leaves); of a leaf's change after the
    steps.  Leaves whose reference gradient is
    under a thousandth of the median leaf's (round-off under Adam) are left
    out of the change."""
    g = leaf_gaps(got["m1"], ref["m1"])
    keep = ref["m1"] >= 1e-3 * np.median(ref["m1"])
    c = np.where(keep, leaf_gaps(got_change, ref_change), 0.0)
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])),
        "grad1_gap": float(g.max()),
        "grad1_median_gap": float(np.median(g)),
        "grad1_p75_gap": float(np.percentile(g, 75)),
        "change_gap": float(c.max()),
        "worst_grad1_leaf": names[int(np.argmax(g))],
        "worst_change_leaf": names[int(np.argmax(c))],
        "leaves_left_out": [names[i] for i in np.flatnonzero(~keep)],
    }


def data_check(ref: traffic.Reference, identities: List[Dict[str, np.ndarray]],
               sampled: List[Dict[str, np.ndarray]]) -> Dict[str, float]:
    """Rows that are not the reference's or came twice, and the largest
    difference of any leaf of a sampled batch from the reference's rows."""
    seen = collections.Counter(
        traffic.row_digest(t, l) for b in identities for t, l in zip(b["tokens"], b["labels"])
    )
    unknown = sum(c for d, c in seen.items() if d not in ref.index)
    repeated = sum(c - 1 for c in seen.values() if c > 1)
    worst = 0.0
    for b in sampled:
        for r in range(b["tokens"].shape[0]):
            i = ref.index.get(traffic.row_digest(b["tokens"][r], b["labels"][r]))
            if i is None:
                continue  # counted as unknown above
            want = ref.make(i)
            for k, v in want.items():
                d = np.max(np.abs(np.asarray(b[k][r], np.float64) - np.asarray(v, np.float64)))
                worst = max(worst, float(d))
    return {"rows_unknown": unknown, "rows_repeated": repeated, "sampled_max_diff": worst,
            "rows_seen": sum(seen.values())}


def reference_batch(ref: traffic.Reference, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The reference's own rows for the rows of a batch, in its order."""
    rows = []
    for t, l in zip(batch["tokens"], batch["labels"]):
        i = ref.index.get(traffic.row_digest(t, l))
        if i is None:
            raise LookupError("a batch row is not in the traffic's reference")
        rows.append(ref.make(i))
    return {k: np.stack([r[k] for r in rows]) for k in rows[0]}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def device_info(devices: List[Any]) -> Dict[str, Any]:
    """The device as JAX reports it.  ``memory_peak_bytes`` is the fullest
    chip's ``peak_bytes_in_use`` plus its ``peak_bytes_reserved``: on a TPU
    the first counts buffers (weights, optimizer state, batches) and the
    second the memory loaded programs reserve for their temporaries."""
    peak = [sum((d.memory_stats() or {}).get(k, 0) for k in ("peak_bytes_in_use", "peak_bytes_reserved"))
            for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peak))}


def program(cfg: Dict[str, Any]):
    """The system under test for a configuration: its model config, model,
    optimizer config, and the state's init from a key (weights made by
    ``bench.models``, optimizer state by the program)."""
    mod = model_module(cfg)
    mcfg = ModelConfig(**{f.name: cfg[f.name] for f in dataclasses.fields(ModelConfig) if f.name in cfg})
    opt_cfg = AdamWConfig(**cfg["optimizer"])

    def init(k):
        params = mod.init_params(cfg, k)
        return {"params": params, "opt": init_state(params, opt_cfg)}

    return mcfg, build_model(mcfg), opt_cfg, init


def _spec_tree(spec: Dict[str, Any]) -> Dict[str, Any]:
    return {k: jax.ShapeDtypeStruct(shape, jnp.dtype(dt)) for k, (shape, dt) in spec.items()}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t0: float) -> Tuple[Dict[str, Any], List[str]]:
    """One run; returns the result line and the lines of checks."""
    cfg, mix = cell.config, cell.mix
    marks = {"run": time.perf_counter() - t0}  # set-up's phases, seconds since t0
    devices = jax.devices()[: cell.chips]
    use_compile_cache()
    mod = model_module(cfg)
    mcfg, model, opt_cfg, init = program(cfg)
    key = R.seed_key(seed)
    in_spec = _spec_tree(mod.input_spec(cfg, cfg["batch"]))

    mesh = plan = None
    if cfg.get("mesh"):
        from repro.dist import sharding_rules as SR
        from repro.dist.context import use_plan
        from repro.launch.mesh import make_mesh, make_plan

        mesh = make_mesh((cfg["mesh"]["data"], cfg["mesh"]["model"]), ("data", "model"),
                         devices=devices)
        plan = make_plan(mesh)
        shape = jax.eval_shape(init, key)
        state_shard = {"params": SR.make_param_shardings(mesh, shape["params"], mcfg, plan),
                       "opt": SR.make_opt_shardings(mesh, shape["opt"], mcfg, plan)}
        batch_shard = SR.batch_sharding(mesh, plan, in_spec)
        with mesh, use_plan(plan):
            state = jax.jit(init, out_shardings=state_shard)(key)
            step = jax.jit(make_train_step(model, opt_cfg), in_shardings=(state_shard, batch_shard),
                           out_shardings=(state_shard, None), donate_argnums=(0,)
                           ).lower(state, in_spec).compile()
    else:
        with jax.default_device(devices[0]):
            state = jax.jit(init)(key)
        step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0,)).lower(state, in_spec).compile()
    step_temp_bytes = int(step.memory_analysis().temp_size_in_bytes)
    jax.block_until_ready(state)
    marks["weights_and_step"] = time.perf_counter() - t0
    # Work done only for the checks is timed and kept out of setup_s.
    t = time.perf_counter()
    norms = jax.jit(R.leaf_norms).lower(state["opt"]["m"]).compile()
    check_s = time.perf_counter() - t
    names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(state["params"])[0]]

    svc_cfg = cfg["service"]
    svc = start_service(num_workers=svc_cfg["workers"], transport=svc_cfg["transport"],
                        worker_processes=svc_cfg["worker_processes"])
    identities: List[Dict[str, Any]] = []  # identity leaves of every batch
    sampled: List[Dict[str, Any]] = []
    pick = random.Random(f"{seed}/sample")
    n_batches = 0

    def keep(batch: Dict[str, Any]) -> None:
        nonlocal n_batches
        identities.append({k: batch[k] for k in IDENTITY_LEAVES})
        if len(sampled) < SAMPLED_BATCHES:
            sampled.append(batch)
        else:
            j = pick.randrange(n_batches + 1)
            if j < SAMPLED_BATCHES:
                sampled[j] = batch
        n_batches += 1

    waits: List[float] = []
    completions: List[float] = []
    losses: List[Any] = []
    window_mets: List[Dict[str, Any]] = []  # the step's metrics, traced runs only
    trace_dir = OUT / f"trace-{cell.name}-{seed}"
    try:
        dds = traffic.pipeline(mix, cfg, seed, cell.root).distribute(service=svc, processing_mode="dynamic")
        with DeviceFeeder(dds, depth=svc_cfg["feeder_depth"], mesh=mesh, plan=plan) as feeder:
            marks["service"] = time.perf_counter() - t0
            proof_losses, m1 = [], None
            for i in range(PROOF_STEPS):
                b = feeder.next(timeout=NEXT_TIMEOUT_S)
                keep(b)
                state, met = step(state, b)
                proof_losses.append(met["loss"])
                if i == 0:
                    m1 = norms(state["opt"]["m"])
            jax.block_until_ready((state, m1))
            marks["proof_steps"] = time.perf_counter() - t0
            t = time.perf_counter()
            proof_params = jax.device_get(state["params"])
            prog = {"losses": [float(x) for x in jax.device_get(proof_losses)],
                    "m1": np.asarray(jax.device_get(m1), np.float64)}
            check_s += time.perf_counter() - t
            for _ in range(cfg["warmup_batches"]):
                b = feeder.next(timeout=NEXT_TIMEOUT_S)
                keep(b)
                state, met = step(state, b)
            jax.block_until_ready(state)
            fm = feeder.metrics
            deadline = time.perf_counter() + QUEUE_FILL_TIMEOUT_S
            while fm.batches_fetched - fm.steps < svc_cfg["feeder_depth"]:
                if time.perf_counter() > deadline:
                    raise TimeoutError("the feeder's queue did not fill")
                time.sleep(0.01)
            marks["queue_full"] = time.perf_counter() - t0
            setup_s = marks["queue_full"] - check_s

            client = dds.last_client
            if trace:
                hlo = step.as_text()
                step = recording(step, window_mets)
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir))
            before = counters(svc.orchestrator, client, feeder)
            prev = None
            start = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                while True:
                    t = time.perf_counter()
                    with jax.profiler.TraceAnnotation("feeder.next"):
                        b = feeder.next(timeout=NEXT_TIMEOUT_S)
                    waits.append(time.perf_counter() - t)
                    keep(b)
                    with jax.profiler.TraceAnnotation("step.dispatch"):
                        state, met = step(state, b)
                    if prev is not None:
                        with jax.profiler.TraceAnnotation("loss.wait"):
                            prev.block_until_ready()
                        completions.append(time.perf_counter())
                    prev = met["loss"]
                    losses.append(prev)
                    if completions and completions[-1] - start >= seconds:
                        break
                with jax.profiler.TraceAnnotation("loss.wait"):
                    prev.block_until_ready()
                completions.append(time.perf_counter())
            after = counters(svc.orchestrator, client, feeder)
            if trace:
                jax.profiler.stop_trace()
    finally:
        svc.orchestrator.stop()
    device = device_info(devices)
    memory_stats = devices[0].memory_stats()

    window_s = completions[-1] - start
    n = len(completions)
    window_losses = [float(x) for x in jax.device_get(losses)]
    failed = sum(not math.isfinite(x) for x in window_losses)
    steps_ms = [1e3 * x for x in stats.intervals(start, completions)]
    measured = {
        "steps_per_s": n / window_s,
        "step_ms_p90": stats.nearest_rank(steps_ms, 0.9),
        "setup_s": setup_s,
    }

    # -- host copies of what reached the device; then free the program's state
    identities = jax.device_get(identities)
    sampled = jax.device_get(sampled)
    del state, step, norms, b, met, prev, losses
    gc.collect()

    delta = window_delta(before, after)
    reduced = None
    if trace:
        data = T.profile(str(trace_dir))
        events = T.load(data, cpu_stand_in=device["platform"] == "cpu")
        reduced = T.reduce(events, stacks=T.hlo_stacks(hlo))
        prog_view = P.reduce(P.load(data), events)
        del data, hlo
        shutil.rmtree(trace_dir, ignore_errors=True)
        mean_mets = step_metrics(window_mets)
        del window_mets

    # -- checks, after the window
    t0_checks = time.perf_counter()
    ref_data = traffic.reference(mix, cfg, seed, cell.root)
    data = data_check(ref_data, identities, sampled)
    checks = {
        "rows_unknown": (data["rows_unknown"], cell.limits["rows_unknown"]),
        "rows_repeated": (data["rows_repeated"], cell.limits["rows_repeated"]),
        "sampled_max_diff": (data["sampled_max_diff"], cell.limits["sampled_max_diff"]),
    }
    notes = [f"set-up marks (s since start) {marks}; check-only work {check_s:.3f} s, "
             f"left out of setup_s",
             f"memory: the step's temporaries by XLA's memory analysis {step_temp_bytes} bytes; "
             f"first chip's stats after the window {memory_stats}",
             f"rows seen {data['rows_seen']}; program losses {prog['losses']}"]
    t = time.perf_counter()
    try:
        proof = [reference_batch(ref_data, identities[i]) for i in range(PROOF_STEPS)]
    except LookupError as e:  # the model cannot be followed on rows it does not know
        notes.append(f"model not compared: {e}")
        gaps: Dict[str, Any] = {k: None for k in MODEL_NUMBERS}
    else:
        ref = reference_readings(cfg, seed, proof, devices=devices)
        ref_change = change_norms(cfg, seed, ref.pop("params"), devices[0])
        prog_change = change_norms(cfg, seed, proof_params, devices[0])
        gaps = compare(prog, ref, ref_change, prog_change, names)
        notes += [f"reference losses {ref['losses']}",
                  f"worst grad1 leaf {gaps['worst_grad1_leaf']}; worst change leaf "
                  f"{gaps['worst_change_leaf']}; left out of change {gaps['leaves_left_out']}"]
    del proof_params
    notes.append(f"checks took {time.perf_counter() - t0_checks:.1f} s, the model's "
                 f"reference {time.perf_counter() - t:.1f} s; allocator peak with the reference "
                 f"{device_info(devices)['memory_peak_bytes']}")
    notes += [f"reading {k} {gaps[k]!r} (not compared in this cell)"
              for k in MODEL_NUMBERS if k not in cell.limits]
    checks.update({k: (gaps[k], cell.limits[k]) for k in MODEL_NUMBERS if k in cell.limits})
    if trace:
        notes.append(f"scope attribution covers {reduced['coverage']!r} of device busy time; "
                     f"top scopes (device-s) {list(reduced['scopes'].items())[:12]}; "
                     f"program spans {prog_view['spans']}")
    correct = failed == 0 and all(v is not None and v <= lim for v, lim in checks.values())
    lines = notes + [f"check {k} {v!r} limit {lim!r}" for k, (v, lim) in checks.items()]

    result: Dict[str, Any] = {"correct": bool(correct), "attempted": n, "failed": failed}
    if trace:
        run_view = {
            "window_s": window_s, "steps": n, "chips": cell.chips, "counters": delta,
            "wait_s": waits, "trace": reduced, "program": prog_view,
            "scope_s": reduced["scopes"].get, "coverage": reduced["coverage"],
            "step_metrics": mean_mets,
            "scope_costs": getattr(mod, "scope_costs", lambda c, b: {})(cfg, cfg["batch"]),
            "flops_per_step": mod.flops_per_step(cfg, cfg["batch"]),
            "peak_flops_per_s": peaks(device["kind"])["bf16_flops_per_s"],
            "hbm_bytes_per_s": peaks(device["kind"])["hbm_bytes_per_s"],
        }
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(cell.root, m["name"])(run_view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"],
                                 "program_gaps": prog_view["program_gaps"]})
    else:
        result.update(
            metrics={m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                     for m in cell.end_to_end},
            device=device,
        )
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result, lines
