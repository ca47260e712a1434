"""The benchmark's model files: FLOPs per step against the hand counts,
parameter counts and weight layout against the program's models, and the
plain references against the program in float32 at a CPU size."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, refops as R
from bench.models import dense_lm, encdec

ROOT = Path(__file__).resolve().parents[2]
TINY = Path(__file__).resolve().parent / "fixtures" / "tiny" / "bench" / "configs"


def config(path: Path):
    with open(path) as f:
        return json.load(f)


def test_starcoder2_flops_match_the_hand_count():
    cfg = config(ROOT / "bench/configs/starcoder2-3b.json")
    # matmuls 6 x 534.77M weights x 4096 tokens; causal attention 1.24 TFLOP
    matmul = 6 * (4 * 95_944_704 + 150_994_944) * 4096
    attn = 3 * 4 * (4096 * 4097 // 2) * 3072 * 4
    got = dense_lm.flops_per_step(cfg, cfg["batch"])
    assert got == pytest.approx(matmul + attn)
    assert got == pytest.approx(14.38e12, rel=1e-3)


def test_whisper_flops_match_the_hand_count():
    cfg = config(ROOT / "bench/configs/whisper-large-v3.json")
    got = encdec.flops_per_step(cfg, cfg["batch"])
    parts = 5.66e12 + 1.11e12 + 3.40e12 + 0.94e12 + 0.38e12
    assert got == pytest.approx(parts, rel=5e-3)
    assert got == pytest.approx(11.5e12, rel=1e-2)


@pytest.mark.parametrize("name,mod,millions", [
    ("starcoder2-3b", dense_lm, 685.8), ("whisper-large-v3", encdec, 249.9)])
def test_weights_are_laid_out_as_the_program_takes_them(name, mod, millions):
    cfg = config(ROOT / f"bench/configs/{name}.json")
    _, model, _, _ = harness.program(cfg)
    key = jax.random.PRNGKey(0)
    theirs = jax.eval_shape(model.init, key)
    ours = jax.eval_shape(lambda k: mod.init_params(cfg, k), key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    n = sum(x.size for x in jax.tree.leaves(theirs))
    assert n == mod.param_count(cfg)
    assert round(n / 1e6, 1) == millions


@pytest.mark.parametrize("name,mod", [("tiny-lm", dense_lm), ("tiny-asr", encdec)])
def test_reference_matches_the_program_in_float32(name, mod):
    cfg = config(TINY / f"{name}.json")
    cfg.update(dtype="float32", remat="none")
    _, model, _, _ = harness.program(cfg)
    from repro.train import make_loss_fn

    params = mod.init_params(cfg, R.seed_key(3_000_000_019))
    rng = np.random.default_rng(0)
    batch = {k: (rng.integers(1, cfg["vocab_size"], shape).astype(np.int32) if dt == "int32"
                 else rng.standard_normal(shape).astype(np.float32))
             for k, (shape, dt) in mod.input_spec(cfg, cfg["batch"]).items()}
    batch["labels"][0, -3:] = R.PAD_ID
    with jax.default_matmul_precision("highest"):
        (want_total, aux), want_g = jax.value_and_grad(make_loss_fn(model), has_aux=True)(params, batch)

        def total(p):
            return R.mean_loss(mod.loss_sums(cfg, p, batch, R.make_einsum("f32")))["total"]

        got_total, got_g = jax.value_and_grad(total)(params)
    nll = R.mean_loss(mod.loss_sums(cfg, params, batch, R.make_einsum("f32")))["loss"]
    assert float(got_total) == pytest.approx(float(want_total), rel=1e-5)
    assert float(nll) == pytest.approx(float(aux["loss"]), rel=1e-5)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-6)


def test_fp8_control_rounds_every_product():
    a = jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)
    exact = R.make_einsum("f32")("ij,jk->ik", a, a)
    low = R.make_einsum("fp8")("ij,jk->ik", a, a)
    rel = float(jnp.max(jnp.abs(low - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-3 < rel < 0.2
    g = jax.grad(lambda x: R.make_einsum("fp8")("ij,jk->ik", x, a).sum())(a)
    want = jax.grad(lambda x: R.make_einsum("f32")("ij,jk->ik", x, a).sum())(a)
    assert 0 < float(jnp.max(jnp.abs(g - want))) < 0.2 * float(jnp.max(jnp.abs(want)))
