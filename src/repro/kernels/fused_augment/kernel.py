"""Pallas TPU fused crop + horizontal-flip + normalize.

This is the DALI-style "offload preprocessing to the accelerator"
alternative the paper argues against (§2): one kernel fuses the three
per-image ops so the kernel reads each uint8 image once and writes back
only the f32 crop — but it still burns chip cycles the train step wants.

Grid: (B,) — per-image programs, embarrassingly parallel.  The (y0, x0)
crop corner and flip flag ride in scalar prefetch (SMEM).  The image is
channel-major in the kernel (the wrapper transposes (B, H, W, C) to
(B, C, H, W)) so each plane's minor block dims are (H, W), the tiling
Mosaic accepts.  Block tiling: the full (1, C, H, W) uint8 image in VMEM,
output (1, C, out_h, out_w) f32.

Mosaic lowers neither a uint8 -> f32 cast, nor ``rev``, nor a dynamic
slice at an unaligned lane offset, so crop and flip are one-hot selection
matmuls: rows = R · plane with R[i, y] = [y == y0 + i], and columns =
rows · S with S[x, j] = [x == x0 + j] (or x0 + out_w - 1 - j when
flipped).  Pixel values 0..255 and the 0/1 selectors are exact in bf16 and
each output sums one nonzero product in f32, so the crop is exact at the
default (single-pass) matmul precision.  mean/std fold into a single FMA
per channel: out = tile * (1/255/std) + (-mean/std).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels import pallas_compat as pltpu


def _augment_kernel(
    crops_ref, flips_ref,  # scalar prefetch (SMEM): (B, 2) i32, (B,) i32
    img_ref, scale_ref, bias_ref,
    out_ref,
    *,
    out_h: int,
    out_w: int,
):
    b = pl.program_id(0)
    y0 = crops_ref[b, 0]
    x0 = crops_ref[b, 1]
    flip = flips_ref[b]

    C, H, W = img_ref.shape[1:]
    # row selector R (out_h, H) and column selector S (W, out_w)
    rows = jax.lax.broadcasted_iota(jnp.int32, (out_h, H), 0)
    ys = jax.lax.broadcasted_iota(jnp.int32, (out_h, H), 1)
    R = (ys == y0 + rows).astype(jnp.float32)
    xs = jax.lax.broadcasted_iota(jnp.int32, (W, out_w), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (W, out_w), 1)
    cols = jnp.where(flip > 0, out_w - 1 - cols, cols)
    S = (xs == x0 + cols).astype(jnp.float32)
    for c in range(C):
        # Mosaic has no uint8 -> f32 cast: widen through int32 first
        plane = img_ref[0, c].astype(jnp.int32).astype(jnp.float32)  # (H, W)
        tile = jax.lax.dot_general(
            R, plane, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        tile = jax.lax.dot_general(
            tile, S, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (out_h, out_w)
        out_ref[0, c] = tile * scale_ref[c] + bias_ref[c]


def fused_augment_fwd(
    images: jnp.ndarray,  # (B, H, W, C) uint8
    crops: jnp.ndarray,  # (B, 2) int32
    flips: jnp.ndarray,  # (B,) int32
    mean: jnp.ndarray,  # (C,) f32
    std: jnp.ndarray,  # (C,) f32
    *,
    out_h: int,
    out_w: int,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, W, C = images.shape
    scale = (1.0 / (255.0 * std)).astype(jnp.float32)
    bias = (-mean / std).astype(jnp.float32)
    planes = jnp.transpose(images, (0, 3, 1, 2))  # (B, C, H, W)

    kern = functools.partial(_augment_kernel, out_h=out_h, out_w=out_w)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, C, H, W), lambda b, *_: (b, 0, 0, 0)),
                smem,
                smem,
            ],
            out_specs=pl.BlockSpec(
                (1, C, out_h, out_w), lambda b, *_: (b, 0, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((B, C, out_h, out_w), jnp.float32),
        compiler_params=pltpu.compiler_params(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(crops.astype(jnp.int32), flips.astype(jnp.int32), planes, scale, bias)
    return jnp.transpose(out, (0, 2, 3, 1))
