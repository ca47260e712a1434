"""Readings that set a cell's limits: the control and the planted faults.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

For each seed, on the cell's own shapes and rows drawn from its traffic's
reference, the model's float32 reference runs the first steps; then, in its
place, each of these, compared with it by the numbers a run compares and
held to the cell's limits as a run is (``correct``):

* ``control`` — the same reference computed in fp8 (``bench.refops``), one
  precision step below the configuration's bfloat16;
* ``half_batch`` — the reference with half of each batch left out of the
  loss and the mean taken over the rest (half the rows, or with one row,
  half its positions);
* ``no_exchange`` (cells on several chips) — the gradient exchange left
  out, as the first chip sees it: the mean gradient of its own rows alone.

A step that returns its state unchanged reads 1 on ``change_gap`` by
construction and needs no run.  One JSON line per seed and reading goes to
standard output and to ``chiprun_out/control-<cell>.jsonl``.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

PAD_ID = 0


def half_batch(batch: Dict[str, Any]) -> Dict[str, Any]:
    import numpy as np

    labels = np.array(batch["labels"])
    if labels.shape[0] >= 2:
        labels[labels.shape[0] // 2 :] = PAD_ID
    else:
        labels[:, labels.shape[1] // 2 :] = PAD_ID
    return dict(batch, labels=labels)


def first_chip_rows(batch: Dict[str, Any], chips: int) -> Dict[str, Any]:
    """The exchange between chips left out, as the first chip sees it: it
    steps with the mean gradient of its own rows alone."""
    import numpy as np

    labels = np.array(batch["labels"])
    labels[labels.shape[0] // chips :] = PAD_ID
    return dict(batch, labels=labels)


def proof_batches(ref: Any, rows: int, steps: int) -> List[Dict[str, Any]]:
    import numpy as np

    out = []
    for s in range(steps):
        made = [ref.make(s * rows + r) for r in range(rows)]
        out.append({k: np.stack([m[k] for m in made]) for k in made[0]})
    return out


def readings(cell: Any, seed: int, diagnose: bool = False) -> List[Dict[str, Any]]:
    import numpy as np
    from bench import harness, traffic

    cfg = cell.config
    ref = traffic.reference(cell.mix, cfg, seed, cell.root)
    batches = proof_batches(ref, cfg["batch"]["rows"], harness.PROOF_STEPS)

    import jax

    def one(precision: str, bs: List[Dict[str, Any]]) -> Dict[str, Any]:
        r = harness.reference_readings(cfg, seed, bs, precision, jax.devices()[: cell.chips])
        r["change"] = harness.change_norms(cfg, seed, r.pop("params"))
        return r

    names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda k: harness.model_module(cfg).init_params(cfg, k),
                       jax.random.PRNGKey(0)))[0]]
    base = one("f32", batches)
    out = []
    runs = [("control", lambda: one("fp8", batches)),
            ("half_batch", lambda: one("f32", [half_batch(b) for b in batches]))]
    if cell.chips > 1:
        runs.append(("no_exchange",
                     lambda: one("f32", [first_chip_rows(b, cell.chips) for b in batches])))
    if diagnose:
        runs.append(("program_direct", lambda: program_direct(cfg, seed, batches)))
    for what, make in runs:
        r = make()
        gaps = harness.compare(r, base, base["change"], r["change"], names)
        checks = {k: {"value": gaps[k], "limit": cell.limits[k]}
                  for k in harness.MODEL_NUMBERS if k in cell.limits}
        line = {"cell": cell.name, "seed": seed, "reading": what,
                "correct": all(c["value"] <= c["limit"] for c in checks.values()),
                "checks": checks, **gaps,
                "losses": r["losses"], "reference_losses": base["losses"]}
        if diagnose:
            line["leaves"] = names
            line["m1_gaps"] = list(np.abs(r["m1"] - base["m1"]) / np.maximum(base["m1"], np.median(base["m1"])))
            line["change_gaps"] = list(np.abs(r["change"] - base["change"]) / np.maximum(base["change"], np.median(base["change"])))
            line["ref_m1"] = list(base["m1"])
        out.append(line)
    return out


def program_direct(cfg: Dict[str, Any], seed: int, batches: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Diagnosis only: the program's compiled step on the same batches,
    handed to it directly rather than through the service."""
    import jax
    import numpy as np
    from bench import harness, refops as R
    from repro.train import make_train_step

    _, model, opt_cfg, init = harness.program(cfg)
    state = jax.jit(init)(R.seed_key(seed))
    step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0,))
    losses, m1 = [], None
    for i, b in enumerate(batches):
        state, met = step(state, b)
        losses.append(float(met["loss"]))
        if i == 0:
            m1 = np.asarray(jax.jit(R.leaf_norms)(state["opt"]["m"]), np.float64)
    params = state["params"]
    del state
    return {"losses": losses, "m1": m1, "change": harness.change_norms(cfg, seed, params)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--diagnose", action="store_true",
                    help="also per-leaf gaps, and the program's step on the same batches")
    args = ap.parse_args(argv)
    from bench import harness
    import jax

    from repro.train import use_compile_cache

    use_compile_cache()
    cell = harness.load_cell(args.workload)
    print(f"control: {cell.name} on {jax.devices()[0].device_kind}", file=sys.stderr)
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    with open(out_dir / f"control-{cell.name}.jsonl", "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            for line in readings(cell, seed, args.diagnose):
                line["seconds"] = time.perf_counter() - t
                f.write(json.dumps(line) + "\n")
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
