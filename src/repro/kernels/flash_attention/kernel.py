"""Pallas TPU flash attention (blocked GQA, online softmax).

Grid: (B, Hq, num_q_blocks, num_kv_blocks) — the last dimension is
"arbitrary" (sequential), so the online-softmax running state (m, l, acc)
lives in VMEM scratch and is carried across KV blocks; the output block is
emitted on the final KV iteration.

BlockSpec tiling (per grid step, all VMEM), over head-major operands —
the wrapper transposes (B, S, H, D) to (B, H, S, D) so that the two minor
block dims are (rows, D), which is the tiling Mosaic accepts:
  q    (1, 1, block_q, D)     — one q-head tile
  k/v  (1, 1, block_k, D)     — the GQA kv head is q_head // group_size
  out  (1, 1, block_q, D)
  scratch: acc (block_q, D) f32, m/l (block_q, MINOR) f32

block_q/block_k default to 128/256: q·kᵀ tiles are (128, 256) f32 = 128 KiB,
acc is (128, 128) f32 = 64 KiB — comfortably VMEM-resident, and both matmul
dims are multiples of the 128-wide MXU.

Causal masking is block-aware: KV blocks strictly above the diagonal are
skipped (no MXU work), diagonal blocks apply the triangular mask inline.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels import pallas_compat as pltpu

MINOR = 128  # TPU vector lane width; scratch minor dim
NEG_INF = -1e30  # avoids -inf NaN propagation inside masked blocks


def _fa_kernel(
    q_ref, k_ref, v_ref,  # VMEM block refs
    o_ref,
    acc_ref, m_ref, l_ref,  # scratch
    *,
    block_q: int,
    block_k: int,
    sq: int,
    sk: int,
    causal: bool,
    window: int,
    softcap: float,
    q_offset: int,
    scale: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this tile
    q_lo = q_offset + qi * block_q
    k_lo = ki * block_k

    # block-level skip: strictly-above-diagonal (causal) or out-of-window
    run = jnp.asarray(True)
    if causal:
        run &= k_lo <= q_lo + block_q - 1
    if window > 0:
        run &= k_lo + block_k - 1 > q_lo - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]  # (block_q, D)
        k = k_ref[0, 0]  # (block_k, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kv_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kv_pos < sk  # tail padding of the last KV block
        if causal:
            mask &= kv_pos <= q_pos
        if window > 0:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]  # (block_q,)
        m_cur = s.max(axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=-1)
        m_ref[:, 0] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    @pl.when(ki == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,  # (B, Sq, Hq, D)
    k: jnp.ndarray,  # (B, Sk, Hkv, D)
    v: jnp.ndarray,  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    if Sq % block_q:
        q = jnp.pad(q, ((0, 0), (0, nq * block_q - Sq), (0, 0), (0, 0)))
    if Sk % block_k:
        pad = nk * block_k - Sk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # head-major: the (block, D) tile is the minor pair of every block
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    grid = (B, Hq, nq, nk)
    kern = functools.partial(
        _fa_kernel,
        block_q=block_q,
        block_k=block_k,
        sq=Sq,
        sk=Sk,
        causal=causal,
        window=window,
        softcap=softcap,
        q_offset=q_offset,
        scale=1.0 / math.sqrt(D),
    )
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, MINOR), jnp.float32),
            pltpu.VMEM((block_q, MINOR), jnp.float32),
        ],
        compiler_params=pltpu.compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)
