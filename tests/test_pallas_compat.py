"""Drift canary for the Pallas API surface the kernel layer depends on.

The repo supports jax 0.9.0 only.  This file pins every Pallas name the
kernels use so a jax upgrade that renames one fails at a single readable
assert, and documents exactly which surface a port must re-verify.
"""
import pytest

pytest.importorskip("jax", reason="optional [test] dependency")
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.kernels import pallas_compat as compat


class TestCompatShim:
    def test_compiler_params_resolves(self):
        """pltpu.CompilerParams accepts dimension_semantics — the exact
        call every kernel makes."""
        params = compat.compiler_params(
            dimension_semantics=("parallel", "arbitrary")
        )
        assert params is not None

    def test_memory_spaces_exist(self):
        for name in ("VMEM", "SMEM"):
            assert getattr(compat, name, None) is not None, name

    def test_prefetch_scalar_grid_spec_exists(self):
        assert compat.PrefetchScalarGridSpec is not None


class TestPallasCoreSurface:
    """Names from jax.experimental.pallas the kernels call directly."""

    @pytest.mark.parametrize(
        "name",
        ["pallas_call", "BlockSpec", "when", "program_id", "num_programs",
         "cdiv", "dslice", "multiple_of"],
    )
    def test_symbol_exists(self, name):
        assert hasattr(pl, name), (
            f"jax {jax.__version__} dropped pl.{name}; "
            "update repro.kernels.pallas_compat and the kernels"
        )


class TestConventions:
    def test_scratch_shapes_and_when_convention(self):
        """A minimal pallas_call using every convention the real kernels
        rely on: grid + BlockSpec index maps, VMEM scratch carried across a
        sequential grid dim, pl.when guards, and compiler_params — all in
        interpret mode so the canary runs on CPU."""

        def kern(x_ref, o_ref, acc_ref):
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _init():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += x_ref[...]

            @pl.when(i == pl.num_programs(0) - 1)
            def _emit():
                o_ref[...] = acc_ref[...]

        x = jnp.arange(32.0, dtype=jnp.float32).reshape(4, 8)
        out = pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 8), jnp.float32),
            scratch_shapes=[compat.VMEM((1, 8), jnp.float32)],
            compiler_params=compat.compiler_params(
                dimension_semantics=("arbitrary",)
            ),
            interpret=True,
        )(x)
        np.testing.assert_allclose(
            out[0], np.arange(32.0).reshape(4, 8).sum(0)
        )

    def test_scalar_prefetch_convention(self):
        """PrefetchScalarGridSpec: scalar operands land ahead of tensor refs
        and are readable with dynamic indices (decode_attention +
        fused_augment depend on this)."""

        def kern(idx_ref, x_ref, o_ref):
            b = pl.program_id(0)
            o_ref[...] = x_ref[...] * idx_ref[b].astype(jnp.float32)

        x = jnp.ones((2, 8), jnp.float32)
        idx = jnp.asarray([2, 5], jnp.int32)
        out = pl.pallas_call(
            kern,
            grid_spec=compat.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(2,),
                in_specs=[pl.BlockSpec((1, 8), lambda b, *_: (b, 0))],
                out_specs=pl.BlockSpec((1, 8), lambda b, *_: (b, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((2, 8), jnp.float32),
            interpret=True,
        )(idx, x)
        np.testing.assert_allclose(np.asarray(out)[:, 0], [2.0, 5.0])
