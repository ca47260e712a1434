"""The Pallas TPU API surface the kernels use, in the installed jax's spelling.

The repo supports one jax: 0.9.0.  Every kernel in ``repro.kernels``
imports the TPU-specific names it needs from here instead of from
``jax.experimental.pallas.tpu`` directly, so a jax upgrade that renames
one of them is absorbed in exactly one module.

``tests/test_pallas_compat.py`` pins each name against the installed jax,
so an incompatible upgrade fails at one readable assert instead of in
scattered kernel tracebacks.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "JAX_VERSION",
    "VMEM",
    "SMEM",
    "PrefetchScalarGridSpec",
    "compiler_params",
]

JAX_VERSION: str = jax.__version__

# scratch shapes and scalar operands
VMEM = pltpu.VMEM
SMEM = pltpu.SMEM

# grid spec of the scalar-prefetch kernels (decode_attention, fused_augment)
PrefetchScalarGridSpec = pltpu.PrefetchScalarGridSpec


def compiler_params(*, dimension_semantics: tuple[str, ...], **kw):
    """The TPU compiler-params object every kernel passes to pallas_call."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics, **kw)
