"""Pallas TPU flash-decoding: split-K attention over a deep KV cache.

Decode attention is memory-bound — the whole KV cache streams through once
per token.  The kernel splits the cache length S into ``num_splits``
independent segments (grid dim, parallel) so HBM reads of different
segments overlap; each segment computes a partial online-softmax
(m_i, l_i, acc_i).  A cheap jnp combine (O(num_splits) per head) merges
partials into the final output — the classic flash-decoding two-phase plan,
adapted so phase 1 is one Pallas kernel and phase 2 is fused XLA.

Grid: (B, Hkv, num_splits); block tiling (VMEM), over a head-major cache
(the wrapper transposes (B, S, Hkv, D) to (B, Hkv, S, D) so the two minor
block dims are (rows, D), the tiling Mosaic accepts):
  q     (1, 1, G, D)      — all G grouped q-heads of this kv head
  k/v   (1, 1, seg, D)
  out   acc (1, 1, 1, G, D) f32; m/l (1, 1, 1, G, 1) f32 columns

The segment loop over block_s-sized tiles runs INSIDE the kernel
(fori_loop over VMEM loads) so each grid step reads its whole segment while
the MXU works on (G × block_s) tiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels import pallas_compat as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,  # SMEM (B,) — valid cache lengths
    q_ref, k_ref, v_ref,
    acc_ref, m_ref, l_ref,
    *,
    block_s: int,
    seg: int,
    window: int,
    scale: float,
):
    b = pl.program_id(0)
    si = pl.program_id(2)
    G, D = q_ref.shape[2], q_ref.shape[3]
    length = len_ref[b]
    seg_lo = si * seg

    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale  # (G, D)

    nblocks = seg // block_s

    def body(i, carry):
        m, l, acc = carry  # (G, 1), (G, 1), (G, D)
        lo = pl.multiple_of(i * block_s, block_s)  # offset within segment
        k = k_ref[0, 0, pl.dslice(lo, block_s), :]  # (block_s, D)
        v = v_ref[0, 0, pl.dslice(lo, block_s), :]
        s = jax.lax.dot_general(
            q.astype(k.dtype), k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (G, block_s)
        kv_pos = seg_lo + lo + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1
        )
        mask = kv_pos < length
        if window > 0:
            mask &= kv_pos > length - 1 - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, 1), jnp.float32)
    a0 = jnp.zeros((G, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, a0))
    acc_ref[0, 0, 0, :, :] = acc
    m_ref[0, 0, 0, :, :] = m
    l_ref[0, 0, 0, :, :] = l


def decode_attention_fwd(
    q: jnp.ndarray,  # (B, Hq, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,  # (B, S, Hkv, D)
    lengths: jnp.ndarray,  # (B,) int32
    *,
    window: int = 0,
    num_splits: int = 8,
    block_s: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv

    # segment size: multiple of block_s covering S
    num_splits = max(1, min(num_splits, pl.cdiv(S, block_s)))
    seg = pl.cdiv(S, num_splits)
    block_s = min(block_s, seg)
    seg = pl.cdiv(seg, block_s) * block_s  # round seg to block multiple
    S_pad = seg * num_splits
    if S_pad != S:
        pad = S_pad - S
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qg = q.reshape(B, Hkv, G, D)
    k_cache = jnp.swapaxes(k_cache, 1, 2)  # head-major (B, Hkv, S, D)
    v_cache = jnp.swapaxes(v_cache, 1, 2)
    grid = (B, Hkv, num_splits)
    kern = functools.partial(
        _decode_kernel,
        block_s=block_s,
        seg=seg,
        window=window,
        scale=1.0 / math.sqrt(D),
    )
    acc, m, l = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, s, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, seg, D), lambda b, h, s, *_: (b, h, s, 0)),
                pl.BlockSpec((1, 1, seg, D), lambda b, h, s, *_: (b, h, s, 0)),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, 1, 1, G, D), lambda b, h, s, *_: (b, h, s, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, G, 1), lambda b, h, s, *_: (b, h, s, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, G, 1), lambda b, h, s, *_: (b, h, s, 0, 0)
                ),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, num_splits, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, num_splits, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, num_splits, G, 1), jnp.float32),
        ],
        compiler_params=pltpu.compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)

    # phase 2: merge split partials (tiny, fused by XLA)
    m, l = m[..., 0], l[..., 0]  # (B, Hkv, ns, G)
    m_g = m.max(axis=2, keepdims=True)  # (B, Hkv, 1, G)
    w = jnp.exp(m - m_g)  # (B, Hkv, ns, G)
    l_tot = (l * w).sum(axis=2)  # (B, Hkv, G)
    out = (acc * w[..., None]).sum(axis=2) / jnp.maximum(l_tot, 1e-30)[..., None]
    return out.reshape(B, Hq, D).astype(q.dtype)
