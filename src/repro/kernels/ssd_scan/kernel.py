"""Pallas TPU kernel for the mamba2 SSD scan (chunked matmul formulation).

The SSD duality turns the token recurrence into per-chunk matmuls (MXU
work) plus a tiny cross-chunk state recurrence:

  intra-chunk:  Y_d = (C Bᵀ ∘ L ∘ dt) X            (Q×Q)·(Q×P) dots
  state in:     Y_o = (C ∘ exp(cum)) H_prev         (Q×N)·(N×P) dot
  state update: H   = exp(cum_Q) H_prev + (B ∘ w)ᵀ X  (N×Q)·(Q×P) dot

Grid: (B, H, num_chunks); the chunk dimension is sequential ("arbitrary")
and the running state H (N, P) f32 lives in VMEM scratch — the cross-chunk
recurrence never leaves the core.  Operands are head-major (the wrapper
transposes (B, L, H, ·) to (B, H, L, ·)) so the two minor block dims are
(tokens, features), the tiling Mosaic accepts.  Block tiling (VMEM):

  x  (1, 1, Q, P)   dt (1, 1, Q, 1) column and (1, 1, 1, Q) row
  B/C (1, 1, Q, N)  y (1, 1, Q, P)
  final state (1, 1, N, P) emitted on the last chunk

The in-chunk prefix sum of the log-decay is a lower-triangular mask matmul
(Mosaic has no cumsum), taken at f32 precision in both orientations.

Q=chunk (default 128), N=state, P=head dim — all matmul dims are 128-ish,
MXU-aligned for the assigned mamba2 config (N=128, P=64).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels import pallas_compat as pltpu

NEG_INF = -1e30


def _ssd_kernel(
    x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, d_ref,
    y_ref, h_out_ref,
    h_ref,  # scratch: running state (N, P) f32
    *,
    chunk: int,
    length: int,
):
    h = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    Q = chunk
    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)  # (Q, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)  # (1, Q)
    Bm = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (Q, N)
    a = a_ref[h]  # scalar decay rate for this head (SMEM)
    D = d_ref[h]

    # zero padded tail tokens (last chunk when L % Q != 0)
    tok_col = ci * Q + jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    tok_row = ci * Q + jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    valid = tok_col < length  # (Q, 1)
    dt_col = jnp.where(valid, dt_col, 0.0)
    dt_row = jnp.where(tok_row < length, dt_row, 0.0)

    # tri[i, j] = 1 for j <= i: cum = tri · da is the inclusive prefix sum
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )
    trif = tri.astype(jnp.float32)
    exact = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot_general(
        trif, dt_col * a, (((1,), (0,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32,
    )  # (Q, 1)
    cum_row = jax.lax.dot_general(
        dt_row * a, trif, (((1,), (1,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32,
    )  # (1, Q)
    # L[i, j] = exp(cum_i - cum_j) for j <= i else 0  (decay from j+1..i)
    Lmat = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)

    CB = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q) = C_i . B_j
    W = CB * Lmat * dt_row  # weight token j's input into token i's output
    y_diag = jax.lax.dot_general(
        W, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    h_prev = h_ref[...]  # (N, P)
    state_in = Cm * jnp.exp(cum_col)  # (Q, N)
    y_off = jax.lax.dot_general(
        state_in, h_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    y = y_diag + y_off + x * D
    y_ref[0, 0] = jnp.where(valid, y, 0.0).astype(y_ref.dtype)

    # state update: H = exp(cum_Q) H_prev + Σ_j exp(cum_Q - cum_j) dt_j B_j x_jᵀ
    cq = (dt_row * a).sum(axis=1, keepdims=True)  # (1, 1) = cum at Q-1
    w = jnp.exp(cq - cum_col) * dt_col  # (Q, 1)
    bw = Bm * w  # (Q, N)
    h_new = jnp.exp(cq) * h_prev + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (N, P)
    h_ref[...] = h_new

    @pl.when(ci == nc - 1)
    def _emit_state():
        h_out_ref[0, 0, :, :] = h_new


def ssd_scan_fwd(
    x: jnp.ndarray,  # (B, L, H, P)
    dt: jnp.ndarray,  # (B, L, H) — post-softplus step sizes
    a: jnp.ndarray,  # (H,) negative decay rates
    Bm: jnp.ndarray,  # (B, L, H, N)
    Cm: jnp.ndarray,  # (B, L, H, N)
    D: jnp.ndarray,  # (H,) skip gain
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    Bsz, L, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, L)
    nc = pl.cdiv(L, chunk)
    Lp = nc * chunk
    if Lp != L:
        pad = Lp - L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # head-major operands; dt twice, as token column and token row
    x, Bm, Cm = (jnp.swapaxes(t, 1, 2) for t in (x, Bm, Cm))
    dt = jnp.swapaxes(dt.astype(jnp.float32), 1, 2)  # (B, H, Lp)
    grid = (Bsz, H, nc)
    kern = functools.partial(_ssd_kernel, chunk=chunk, length=L)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, h_final = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            smem,
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, Lp, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        x,
        dt[..., None],
        dt[:, :, None, :],
        a.astype(jnp.float32),
        Bm,
        Cm,
        D.astype(jnp.float32),
    )
    return jnp.swapaxes(y[:, :, :L], 1, 2), h_final
