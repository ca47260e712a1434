"""The harness on the CPU: finding cells, configurations, mixes and metric
readers by name; the window arithmetic; the last line of a run; and the
command refusing to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, stats

ROOT = Path(__file__).resolve().parents[2]


def test_every_cell_of_the_benchmark_is_found_with_its_files():
    spec = json.load(open(ROOT / "BENCHMARK.json"))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert harness.model_module(cell.config).flops_per_step(cell.config, cell.config["batch"]) > 0
        assert set(cell.limits) >= {"change_gap", "rows_unknown", "rows_repeated", "sampled_max_diff"}
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert callable(harness.load_reader(ROOT, m["name"]))
    for c in spec["configs"]:
        cfg = json.load(open(ROOT / c["file"]))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"]) == sorted(cfg["published"])


def test_a_new_configuration_and_metric_are_found_from_their_files(tiny_root):
    """Files the harness has never been told about: a configuration, a mix,
    limits and a per-layer metric, added with BENCHMARK.json entries only."""
    b = tiny_root / "bench"
    cfg = json.load(open(b / "configs" / "tiny-lm.json"))
    cfg.update(name="tiny-lm-wide", d_ff=256)
    json.dump(cfg, open(b / "configs" / "tiny-lm-wide.json", "w"))
    json.dump(json.load(open(b / "traffic" / "lm-tiny.json")), open(b / "traffic" / "lm-tiny-2.json", "w"))
    json.dump(json.load(open(b / "limits" / "tiny-lm.lm-tiny.json")),
              open(b / "limits" / "tiny-lm-wide.lm-tiny-2.json", "w"))
    (b / "metrics" / "step.rows_per_s.py").write_text(
        "def read(run):\n    return run['steps'] * 2 / run['window_s']\n")
    spec = json.load(open(tiny_root / "BENCHMARK.json"))
    spec["configs"].append({"name": "tiny-lm-wide", "source": "test", "reduced": [], "why": "test",
                            "file": "bench/configs/tiny-lm-wide.json"})
    spec["workloads"].append({"name": "tiny-lm-wide.lm-tiny-2", "config": "tiny-lm-wide",
                              "traffic": "lm-tiny-2", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "step.rows_per_s", "unit": "rows/s", "better": "higher",
                              "source": "host_clock", "layer": "step", "moves": "steps_per_s",
                              "workloads": ["tiny-lm-wide.lm-tiny-2"]})
    json.dump(spec, open(tiny_root / "BENCHMARK.json", "w"))

    cell = harness.load_cell("tiny-lm-wide.lm-tiny-2", tiny_root)
    assert cell.config["d_ff"] == 256
    assert "step.rows_per_s" in [m["name"] for m in cell.per_layer]
    assert "step.rows_per_s" not in [m["name"] for m in harness.load_cell("tiny-lm.lm-tiny", tiny_root).per_layer]
    read = harness.load_reader(tiny_root, "step.rows_per_s")
    assert read({"steps": 10, "window_s": 4.0}) == 5.0


NEW_KIND = """
import numpy as np

from bench.traffic import Reference


def row(i, *, seq_len, vocab, seed):
    out = np.random.default_rng((seed, int(i), 7)).integers(1, vocab, seq_len + 1).astype(np.int32)
    return {"tokens": out[:-1], "labels": out[1:]}


def pipeline(mix, cfg, seed):
    from repro.data import Dataset

    return (Dataset.range(mix["num_sequences"])
            .map(row, seq_len=cfg["batch"]["seq_len"], vocab=cfg["vocab_size"], seed=seed)
            .batch(cfg["batch"]["rows"], drop_remainder=True))


def reference(mix, cfg, seed):
    def make(i):
        return row(i, seq_len=cfg["batch"]["seq_len"], vocab=cfg["vocab_size"], seed=seed)

    return Reference(mix["num_sequences"], make, lambda i: (make(i)["tokens"], make(i)["labels"]))
"""


def test_a_new_kind_of_traffic_is_found_from_its_file(tiny_root, no_compile_cache):
    """A kind of traffic the harness has never been told about, in a module of
    its own, drives a whole run on the CPU, its pool children included."""
    b = tiny_root / "bench"
    (b / "kinds" / "lm_uniform.py").write_text(NEW_KIND)
    json.dump({"kind": "lm_uniform", "num_sequences": 512}, open(b / "traffic" / "lm-uniform.json", "w"))
    json.dump(json.load(open(b / "limits" / "tiny-lm.lm-tiny.json")),
              open(b / "limits" / "tiny-lm.lm-uniform.json", "w"))
    spec = json.load(open(tiny_root / "BENCHMARK.json"))
    spec["workloads"].append({"name": "tiny-lm.lm-uniform", "config": "tiny-lm",
                              "traffic": "lm-uniform", "chips": 1, "why": "test"})
    json.dump(spec, open(tiny_root / "BENCHMARK.json", "w"))

    cell = harness.load_cell("tiny-lm.lm-uniform", tiny_root)
    result, lines = harness.run(cell, 3_000_000_027, 0.3, False, 0.0)
    assert result["correct"] is True, lines
    assert result["checks"]["rows_unknown"]["value"] == 0
    assert any(line.startswith("rows seen ") for line in lines)


def test_intervals_and_p90_by_nearest_rank():
    assert stats.intervals(1.0, [1.5, 2.5, 2.75]) == [0.5, 1.0, 0.25]
    values = list(range(1, 101))
    assert stats.nearest_rank(values, 0.9) == 90
    assert stats.nearest_rank([3.0, 1.0, 2.0], 0.9) == 3.0
    assert stats.nearest_rank([7.0], 0.9) == 7.0
    with pytest.raises(ValueError):
        stats.nearest_rank([], 0.9)


def test_readers_compute_from_the_window_counters():
    run = {"window_s": 10.0, "steps": 30, "chips": 1, "wait_s": [0.001] * 9 + [0.5],
           "flops_per_step": 14.38e12, "peak_flops_per_s": 197e12,
           "counters": {"worker_cpu_s": 0.6, "worker_batches": 30, "client_fetch_s": 0.3,
                        "client_batches": 30, "client_shm_batches": 24, "feed_transfer_s": 0.09,
                        "feed_steps": 30},
           "trace": {"window_s": 10.0, "busy_s": 9.5, "collective_exposed_s": 0.3}}
    read = lambda name: harness.load_reader(ROOT, name)(run)  # noqa: E731
    assert read("worker.cpu_ms_per_batch") == pytest.approx(20.0)
    assert read("client.fetch_ms_per_batch") == pytest.approx(10.0)
    assert read("client.shm_share") == pytest.approx(80.0)
    assert read("feed.transfer_ms_per_step") == pytest.approx(3.0)
    assert read("feed.wait_ms_p90") == pytest.approx(1.0)
    assert read("step.mfu") == pytest.approx(100 * 14.38e12 * 3 / 197e12)
    assert read("device.idle_share") == pytest.approx(5.0)
    assert read("step.allreduce_exposed_ms") is None  # one chip
    assert harness.load_reader(ROOT, "step.allreduce_exposed_ms")(dict(run, chips=4)) == pytest.approx(10.0)
    assert harness.load_reader(ROOT, "device.idle_share")(dict(run, trace=None)) is None


def test_the_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "starcoder2-3b.lm-packed-4k",
         "--seed", "4294967311", "--seconds", "10", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


@pytest.mark.parametrize("cell", ["tiny-lm.lm-tiny", "tiny-asr.asr-tiny"])
def test_a_run_on_the_cpu_prints_the_contract_keys_and_is_correct(cell, tiny_root, no_compile_cache):
    c = harness.load_cell(cell, tiny_root)
    result, lines = harness.run(c, 3_000_000_021, 0.3, False, 0.0)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"steps_per_s", "step_ms_p90", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert lines[-len(result["checks"]):] == [
        f"check {k} {v['value']!r} limit {v['limit']!r}" for k, v in result["checks"].items()]
    json.dumps(result)


READERS = {
    "extra.span_count": "def read(run):\n"
                        "    s = run['program']['spans'].get('bench_test.extra')\n"
                        "    return None if s is None else s['count']\n",
    "extra.counter": "def read(run):\n    return run['counters']['bench_test_extra_total']\n",
    "extra.scope_share": "def read(run):\n"
                         "    t = run['scope_s']('bench_test_scope')\n"
                         "    busy = run['trace']['busy_s'] * run['trace']['devices']\n"
                         "    return None if not t else 100.0 * t / busy\n",
    "extra.step_metric": "def read(run):\n    return run['step_metrics'].get('bench_test_rows')\n",
}


def test_a_new_span_counter_and_named_scope_reach_reader_files(tiny_root, no_compile_cache,
                                                               monkeypatch):
    """The files-only route: the program opens a span, bumps a counter, runs
    a named scope and returns a metric that the benchmark was never told
    about, and reader files added beside the others read each from a traced
    run on the CPU."""
    import jax
    import jax.numpy as jnp

    from repro.feed import DeviceFeeder
    from repro.obs.registry import get_registry
    from repro.obs.tracing import annotate

    real_next, real_step = DeviceFeeder.next, harness.make_train_step

    def next_with_span(self, *a, **kw):
        with annotate("bench_test.extra"):
            get_registry().counter("bench_test_extra_total").add(1)
            return real_next(self, *a, **kw)

    def scoped(model, opt):
        step = real_step(model, opt)

        def call(state, batch):
            with jax.named_scope("bench_test_scope"):
                state, met = step(state, batch)
                rows = jnp.float32(batch["labels"].shape[0])
            return state, dict(met, bench_test_rows=rows)

        return call

    monkeypatch.setattr(DeviceFeeder, "next", next_with_span)
    monkeypatch.setattr(harness, "make_train_step", scoped)
    # the CPU is not in the table of peaks, and a device missing there is an error
    monkeypatch.setattr(harness, "peaks", lambda kind: {"bf16_flops_per_s": 1e12,
                                                        "hbm_bytes_per_s": 1e11})
    spec = json.load(open(tiny_root / "BENCHMARK.json"))
    for name, text in READERS.items():
        (tiny_root / "bench" / "metrics" / f"{name}.py").write_text(text)
        spec["per_layer"].append({"name": name, "unit": "1", "better": "higher",
                                  "source": "program_span", "layer": "test",
                                  "moves": "steps_per_s", "workloads": ["tiny-lm.lm-tiny"]})
    json.dump(spec, open(tiny_root / "BENCHMARK.json", "w"))

    result, lines = harness.run(harness.load_cell("tiny-lm.lm-tiny", tiny_root), 3_000_000_031,
                                0.5, True, 0.0)
    assert result["correct"] is True, lines
    got = {k: v["value"] for k, v in result["metrics"].items()}
    n = result["attempted"]
    assert got["extra.span_count"] >= n  # one per feeder.next in the window
    assert got["extra.counter"] >= n
    assert 0 < got["extra.scope_share"] <= 100
    assert got["extra.step_metric"] == 2.0
    assert "bench_test.extra" in dict(result["breakdown"]["program_gaps"]) or \
        result["breakdown"]["program_gaps"] == []
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps", "program_gaps"}
    assert any(line.startswith("scope attribution covers ") for line in lines)
    json.dumps(result)
