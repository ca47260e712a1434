"""Compile every Pallas kernel for a described TPU v5e at real widths.

Interpret mode (``tests/test_kernels.py``) checks the kernels' math on the
CPU but not what Mosaic accepts: block tiling, casts and primitives.  Each
test here lowers and compiles one kernel for one chip of a ``v5e:2x2``
topology that is described, not attached, so a kernel the chip's compiler
would refuse fails here at no chip time.  Nothing runs; results and
timings come only from ``python chip_smoke.py`` on the chip.

Widths: flash_attention at StarCoder2-3B (1×4096, 24 query / 2 KV heads,
head dim 128, window 4096); decode_attention at the same heads over a
32k cache; ssd_scan at mamba2-2.7b (H=80, P=64, N=128); fused_augment on
128 RGB 256² images cropped to 224²; moe_router over 4096 tokens × 64
experts, top-6.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""
import pytest

pytest.importorskip("jax", reason="optional [test] dependency")
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention

    q = jax.ShapeDtypeStruct((1, 4096, 24, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 2, 128), jnp.bfloat16, sharding=one_chip)
    _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096),
        q, kv, kv,
    )


def test_decode_attention_compiles(one_chip):
    from repro.kernels.decode_attention.ops import decode_attention

    q = jax.ShapeDtypeStruct((8, 24, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 32768, 2, 128), jnp.bfloat16, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    _compile(lambda q, k, v, n: decode_attention(q, k, v, n), q, kv, kv, lens)


def test_ssd_scan_compiles(one_chip):
    from repro.kernels.ssd_scan.ops import ssd_scan

    B, L, H, P, N = 1, 4096, 80, 64, 128

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    _compile(
        lambda *a: ssd_scan(*a),
        s(B, L, H, P), s(B, L, H), s(H), s(B, L, H, N), s(B, L, H, N), s(H),
    )


def test_fused_augment_compiles(one_chip):
    from repro.kernels.fused_augment.ops import fused_augment

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(
        lambda i, c, f, m, sd: fused_augment(i, c, f, m, sd, out_h=224, out_w=224),
        s((128, 256, 256, 3), jnp.uint8), s((128, 2), jnp.int32),
        s((128,), jnp.int32), s((3,), jnp.float32), s((3,), jnp.float32),
    )


def test_moe_router_compiles(one_chip):
    from repro.kernels.moe_router.ops import moe_router

    logits = jax.ShapeDtypeStruct((4096, 64), jnp.float32, sharding=one_chip)
    _compile(lambda x: moe_router(x, k=6, capacity=4096), logits)
