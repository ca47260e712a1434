"""The chip smoke's served-path phase and its checks, on the CPU at a tiny size.

``chip_smoke.py`` refuses to run without a TPU, but only in ``main()``: the
served phase itself (service → pool children → shm ring → DeviceFeeder →
jitted train step) and the checks that judge it are plain functions, so
they run here on ``scaled_down()`` StarCoder2 with 64-token sequences; the
``--chips 4`` phase runs on four forced CPU devices in a child process.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytest.importorskip("jax", reason="optional [test] dependency")
import numpy as np

from repro.configs import get_config
from repro.core import start_service
from repro.data import Dataset

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module  # dataclasses resolve their module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def served(smoke):
    cfg = get_config("starcoder2-3b").scaled_down()
    return smoke.run_served(cfg, seq_len=64, batch_size=1, warmup=2, steps=4)


def test_served_phase_passes_its_checks(smoke, served):
    smoke.check_served(served)


def test_device_batches_match_the_local_reference_once_each(served):
    assert sum(served.reference.values()) == 6
    assert served.device_rows == served.reference
    assert not served.extra_batch


def test_batches_come_through_the_shm_ring_from_pool_children(served):
    assert served.shm_batches > 0
    assert served.children > 0
    assert served.fallbacks == 0


def test_checks_reject_a_repeated_batch(smoke, served):
    import collections
    import dataclasses

    digest = next(iter(served.reference))
    repeated = dataclasses.replace(
        served, device_rows=served.device_rows + collections.Counter([digest])
    )
    with pytest.raises(smoke.CheckFailed, match="repeated"):
        smoke.check_served(repeated)


_PARENT = os.getpid()


def _die_in_child(i):
    if os.getpid() != _PARENT:
        os._exit(3)  # a pool child lost before its first element
    return np.full((2,), i, dtype=np.int64)


def test_child_death_before_first_element_moves_the_fallback_counter(smoke):
    svc = start_service(num_workers=1, transport="tcp", worker_processes=2)
    try:
        dds = Dataset.range(16).map(_die_in_child).distribute(
            service=svc, processing_mode="dynamic"
        )
        got = sorted(int(v) for e in dds.session() for v in np.ravel(e))
        assert got == sorted(v for i in range(16) for v in [i] * 2)
        assert smoke.pool_fallbacks(svc.orchestrator) > 0
    finally:
        svc.orchestrator.stop()


_SHARDED = r"""
import dataclasses, importlib.util, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
sys.modules["chip_smoke"] = smoke
spec.loader.exec_module(smoke)
from repro.configs import get_config

# at this size agreeing leaves differ by up to ~1e-2 and a dropped row
# moves every leaf by ~0.17 or more: the bound sits between them
rtol = 4e-2
cfg = get_config("starcoder2-3b").scaled_down().replace(dtype="bfloat16")
run = smoke.run_sharded(cfg, devices=jax.devices()[:4], seq_len=64, steps=3)
smoke.check_sharded(run, rtol)
faulty = dataclasses.replace(run, leaf_err=run.planted_err)
try:
    smoke.check_sharded(faulty, rtol)
    caught = False
except smoke.CheckFailed:
    caught = True
print(json.dumps({
    "rows": run.rows_per_device,
    "worst": max(run.leaf_err.values()),
    "planted_min": min(run.planted_err.values()),
    "caught": caught,
}))
"""


def test_sharded_phase_on_four_cpu_devices_catches_a_dropped_row(tmp_path):
    """The --chips 4 phase on 4 forced CPU devices with bf16 compute: mesh
    gradients match the one-device microbatched ones leaf by leaf, and the
    same comparison fails when one batch row is dropped on the mesh.  Its
    own process: XLA_FLAGS must be set before jax initializes."""
    script = tmp_path / "sharded.py"
    script.write_text(_SHARDED)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(script), str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["rows"] == [[1, 1, 1, 1]] * 3
    assert out["worst"] < out["planted_min"] / 4
    assert out["caught"]
