"""Distribution layer: sharding rules on a tiny real mesh, HLO cost analyzer
correctness (trip counts, 6·N·D anchoring), serve engine behavior."""
import pytest

pytest.importorskip("jax", reason="optional [test] dependency")
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist import sharding_rules as SR
from repro.dist.context import ShardingPlan
from repro.launch import hlo_cost
from repro.launch.mesh import make_plan, make_test_mesh
from repro.launch.roofline import parse_collective_bytes
from repro.models import build_model


class TestShardingRules:
    def test_param_specs_cover_all_leaves(self):
        mesh = make_test_mesh(1, 1)
        plan = make_plan(mesh)
        for arch in ("qwen3-14b", "kimi-k2-1t-a32b", "mamba2-2.7b",
                     "jamba-v0.1-52b", "whisper-large-v3"):
            cfg = get_config(arch).scaled_down()
            model = build_model(cfg)
            pshape = jax.eval_shape(lambda m=model: m.init(jax.random.PRNGKey(0)))
            shardings = SR.make_param_shardings(mesh, pshape, cfg, plan)
            n_leaves = len(jax.tree.leaves(pshape))
            n_shards = len(jax.tree.leaves(
                shardings, is_leaf=lambda x: hasattr(x, "spec")
            ))
            assert n_leaves == n_shards

    def test_indivisible_dims_fall_back_to_replication(self):
        mesh = make_test_mesh(1, 1)
        if mesh is None:
            pytest.skip("needs 1 device")
        plan = ShardingPlan(data_axes=("data",), model_axis="model",
                            fsdp_axis="data", seq_axis=None)
        # head_dim 7 is not divisible by any axis size > 1 — must not crash
        cfg = get_config("qwen3-14b").scaled_down()
        spec = SR.param_spec(
            (jax.tree_util.DictKey("attn"), jax.tree_util.DictKey("wq")),
            jax.ShapeDtypeStruct((7, 13), jnp.float32), cfg, plan, mesh,
        )
        assert spec is not None  # P(None-ish) acceptable on 1-dev mesh

    def test_train_step_runs_sharded_on_test_mesh(self):
        """jit with explicit shardings on a real (1,1) mesh — the same code
        path the dry-run uses at (16,16)."""
        from repro.launch import specs as S
        from repro.models.config import ShapeConfig
        from repro.train import AdamWConfig, init_train_state, make_train_step

        mesh = make_test_mesh(1, 1)
        plan = make_plan(mesh)
        cfg = get_config("deepseek-7b").scaled_down()
        model = build_model(cfg)
        state = init_train_state(model, jax.random.PRNGKey(0), AdamWConfig())
        pshape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        p_shard = SR.make_param_shardings(mesh, pshape, cfg, plan)
        in_specs = S.train_input_specs(cfg, ShapeConfig("t", 32, 2, "train"))
        b_shard = SR.batch_sharding(mesh, plan, in_specs)
        ostate = jax.eval_shape(
            lambda: init_train_state(model, jax.random.PRNGKey(0), AdamWConfig())
        )
        state_shard = {
            "params": p_shard,
            "opt": SR.make_opt_shardings(
                mesh, ostate["opt"], cfg, plan
            ),
        }
        step = make_train_step(model, AdamWConfig())
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 32))),
            "labels": jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 32))),
        }
        with mesh:
            jstep = jax.jit(step, in_shardings=(state_shard, b_shard))
            new_state, metrics = jstep(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))


class TestHloCostAnalyzer:
    def test_dot_flops_exact(self):
        M, K, N = 64, 128, 32

        def f(a, b):
            return a @ b

        hlo = (
            jax.jit(f)
            .lower(
                jax.ShapeDtypeStruct((M, K), jnp.float32),
                jax.ShapeDtypeStruct((K, N), jnp.float32),
            )
            .compile()
            .as_text()
        )
        cost = hlo_cost.analyze(hlo)
        assert cost.flops == pytest.approx(2 * M * K * N, rel=0.01)

    def test_scan_trip_count_multiplies(self):
        """cost_analysis counts while bodies once; ours multiplies by trips."""
        L, M = 8, 32

        def f(ws, x):
            def body(c, w):
                return jnp.tanh(c @ w), None

            y, _ = jax.lax.scan(body, x, ws)
            return y

        hlo = (
            jax.jit(f)
            .lower(
                jax.ShapeDtypeStruct((L, M, M), jnp.float32),
                jax.ShapeDtypeStruct((M, M), jnp.float32),
            )
            .compile()
            .as_text()
        )
        cost = hlo_cost.analyze(hlo)
        assert cost.flops == pytest.approx(L * 2 * M * M * M, rel=0.05)
        assert L in cost.while_trips

    def test_6nd_anchor_dense_lm(self):
        """Dense LM train step HLO flops ≈ 6·N·D within remat slack."""
        from repro.train import AdamWConfig, make_train_step

        cfg = get_config("deepseek-7b").scaled_down().replace(remat="none")
        model = build_model(cfg)
        step = make_train_step(model, AdamWConfig())
        B, S = 4, 128
        state_shape = jax.eval_shape(
            lambda: {
                "params": model.init(jax.random.PRNGKey(0)),
                "opt": __import__("repro.train.optimizer", fromlist=["o"]).init_state(
                    model.init(jax.random.PRNGKey(0)), AdamWConfig()
                ),
            }
        )
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        hlo = jax.jit(step).lower(state_shape, batch).compile().as_text()
        cost = hlo_cost.analyze(hlo)
        n = cfg.param_counts()["active"]
        model_flops = 6.0 * n * B * S
        ratio = cost.flops / model_flops
        # embed/attention overhead push above 1; should be the right magnitude
        assert 0.8 < ratio < 3.0, ratio

    def test_collective_parse_synthetic_hlo(self):
        """A 1-device mesh compiles psum away, so feed the parser the HLO
        shapes it sees in the real 256-device dry-run artifacts."""
        hlo = """
ENTRY %main (p0: bf16[16,1024]) -> bf16[16,1024] {
  %p0 = bf16[16,1024]{1,0} parameter(0)
  %ag = bf16[256,1024]{1,0} all-gather(bf16[16,1024]{1,0} %p0), dimensions={0}
  %ar = bf16[16,1024]{1,0} all-reduce(bf16[16,1024]{1,0} %p0), to_apply=%add
  %rs = bf16[1,1024]{1,0} reduce-scatter(bf16[16,1024]{1,0} %p0), dimensions={0}, to_apply=%add
}
"""
        coll = parse_collective_bytes(hlo)
        assert coll["all-gather"] == 16 * 1024 * 2
        assert coll["all-reduce"] == 16 * 1024 * 2
        assert coll["reduce-scatter"] == 16 * 1024 * 2
        assert coll["counts"]["all-gather"] == 1

    def test_hlo_cost_collectives_trip_weighted(self):
        """Collectives inside a scan body are weighted by the trip count."""
        hlo = """
%body (arg: (s32[], bf16[64,64])) -> (s32[], bf16[64,64]) {
  %arg = (s32[], bf16[64,64]) parameter(0)
  %g = bf16[64,64]{1,0} get-tuple-element(%arg), index=1
  %ar = bf16[64,64]{1,0} all-reduce(%g), to_apply=%add
  ROOT %t = (s32[], bf16[64,64]) tuple(%arg, %ar)
}
%cond (arg: (s32[], bf16[64,64])) -> pred[] {
  %arg = (s32[], bf16[64,64]) parameter(0)
  ROOT %lt = pred[] constant(1)
}
ENTRY %main (p: bf16[64,64]) -> bf16[64,64] {
  %p = bf16[64,64]{1,0} parameter(0)
  %w = (s32[], bf16[64,64]) while(%p), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
  ROOT %r = bf16[64,64]{1,0} get-tuple-element(%w), index=1
}
"""
        cost = hlo_cost.analyze(hlo)
        assert cost.collective_counts["all-reduce"] == 12
        assert cost.collective_bytes == 12 * 64 * 64 * 2


class TestAutoscaler:
    def test_scales_up_under_backlog(self, service_factory):
        from repro.core import Autoscaler, AutoscalerConfig

        svc = service_factory(num_workers=1)
        orch = svc.orchestrator
        scaler = Autoscaler(
            orch,
            AutoscalerConfig(min_workers=1, max_workers=4,
                             scale_out_threshold=1.1,  # always "starved"
                             cooldown_s=0.0),
        )
        # run a job so occupancy signals exist, then step the scaler
        from repro.data import Dataset

        ds = Dataset.range(100).map(lambda x: x).batch(1).distribute(
            service=svc, processing_mode="off"
        )
        it = iter(ds)
        for _ in range(3):
            next(it)
        n = scaler.step()
        assert n >= 1
        assert len(orch.live_workers) >= 1


class TestRooflinePeaks:
    def test_v5e_peaks_by_device_kind(self):
        from repro.launch.roofline import peaks_for

        peak = peaks_for("TPU v5 lite")
        assert (peak.flops, peak.hbm_bw) == (197e12, 819e9)

    def test_unknown_device_kind_is_an_error(self):
        from repro.launch.roofline import peaks_for

        with pytest.raises(ValueError, match="no published peaks"):
            peaks_for("cpu")
