"""The harness on a data-parallel mesh of four forced CPU devices, in a child
process (the device count is fixed when JAX starts): a sound run is
correct, and a run whose step leaves out the exchange between chips is not."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"

CHILD = r"""
import json, shutil, sys, tempfile
from pathlib import Path
sys.path.insert(0, sys.argv[1])
from bench import harness

root = Path(tempfile.mkdtemp()) / "checkout"
shutil.copytree(sys.argv[2], root)
for part in ("metrics", "kinds"):
    shutil.copytree(Path(sys.argv[1]) / "bench" / part, root / "bench" / part)
harness.use_compile_cache = lambda: ""
cell = harness.load_cell("tiny-lm-dp4.lm-tiny", root)
out = {}
out["sound"] = harness.run(cell, 3_000_000_029, 0.3, False, 0.0)[0]

real = harness.make_train_step


def no_exchange(model, opt):
    step = real(model, opt)
    chips = cell.chips

    def broken(state, batch):
        # without the exchange the first chip steps on the mean gradient of
        # its own rows; its state is what the run reads
        labels = batch["labels"]
        return step(state, dict(batch, labels=labels.at[labels.shape[0] // chips:].set(0)))

    return broken


harness.make_train_step = no_exchange
out["no_exchange"] = harness.run(cell, 3_000_000_029, 0.3, False, 0.0)[0]
shutil.rmtree(root.parent)
print(json.dumps(out))
"""


def test_mesh_run_is_correct_and_catches_a_missing_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT), str(FIXTURES / "tiny")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    sound, broken = out["sound"], out["no_exchange"]
    assert sound["device"]["count"] == 4
    assert sound["correct"] is True, sound["checks"]
    assert broken["correct"] is False, broken["checks"]
