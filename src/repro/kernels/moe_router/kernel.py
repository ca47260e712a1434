"""Pallas TPU fused MoE router: softmax + top-k + capacity slot assignment.

One kernel replaces four XLA ops (softmax, top_k, one_hot+cumsum dispatch
bookkeeping) and keeps the (T, E) probability tile VMEM-resident throughout.

Grid: (num_token_blocks,) — sequential ("arbitrary"), because slot
assignment is a running per-expert counter carried in VMEM scratch across
blocks.  Block tiling:
  logits (block_t, E) in VMEM;  outputs ids/gates/slots (block_t, k)
  scratch counts (1, E) int32 — the per-expert fill level

Top-k is k rounds of (max, argmax, mask) over the VMEM tile — k ≤ 8 for
every assigned MoE config, so the loop is fully unrolled vector work.
Slot assignment is token-major over the flattened (T·k) choice list —
bit-identical to the gshard exclusive cumsum in ``models.layers.moe_ffn``:
slot(t, j) = counts_before[e] + #{(t', j'): t' < t, id = e}
                              + #{(t, j'): j' < j, id = e}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels import pallas_compat as pltpu


def _router_kernel(
    logits_ref,
    ids_ref, gates_ref, slots_ref,
    counts_ref,  # scratch (1, E) int32
    *,
    k: int,
    block_t: int,
    total_t: int,
):
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    logits = logits_ref[...].astype(jnp.float32)  # (Tb, E)
    Tb, E = logits.shape
    # mask padded tail tokens so they never win capacity slots
    tok = bi * block_t + jax.lax.broadcasted_iota(jnp.int32, (Tb, 1), 0)
    valid = tok < total_t  # (Tb, 1)

    m = logits.max(axis=-1, keepdims=True)
    ex = jnp.exp(logits - m)
    probs = ex / ex.sum(axis=-1, keepdims=True)

    eids = jax.lax.broadcasted_iota(jnp.int32, (Tb, E), 1)
    counts = counts_ref[0, :]  # (E,)

    # phase 1: top-k winners (unrolled: k ≤ 8)
    gate_cols = []
    onehots = []
    for j in range(k):
        g = probs.max(axis=-1)  # (Tb,)
        win = probs == g[:, None]  # ties -> lowest expert id wins
        idx = jnp.where(win, eids, E).min(axis=-1)  # (Tb,)
        onehots.append(((eids == idx[:, None]) & valid).astype(jnp.int32))
        ids_ref[:, j] = idx
        gate_cols.append(g)
        probs = jnp.where(eids == idx[:, None], -1.0, probs)

    # phase 2: token-major slot assignment.  For (t, j):
    #   counts[e] + Σ_{t'<t} any-choice[t', e] + Σ_{j'<j} onehot_j'[t, e]
    all_choices = onehots[0]
    for j in range(1, k):
        all_choices = all_choices + onehots[j]  # (Tb, E) ∈ {0,1}
    # exclusive prefix sum over tokens as a strictly-lower-triangular mask
    # matmul (Mosaic has no cumsum); 0/1 operands are exact in bf16 and
    # sums <= block_t exact in the f32 accumulator, so the default
    # (single-pass) precision is exact
    strict = (
        jax.lax.broadcasted_iota(jnp.int32, (Tb, Tb), 0)
        > jax.lax.broadcasted_iota(jnp.int32, (Tb, Tb), 1)
    ).astype(jnp.float32)
    before_tok = jax.lax.dot_general(
        strict, all_choices.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    prior_round = jnp.zeros_like(all_choices)
    for j in range(k):
        pos = counts[None, :] + before_tok + prior_round
        slots_ref[:, j] = (pos * onehots[j]).sum(axis=-1)
        prior_round = prior_round + onehots[j]
    counts_ref[0, :] = counts + all_choices.sum(axis=0)

    gates = jnp.stack(gate_cols, axis=1)  # (Tb, k)
    gates_ref[...] = gates / jnp.maximum(gates.sum(axis=1, keepdims=True), 1e-9)


def moe_router_fwd(
    logits: jnp.ndarray,  # (T, E)
    k: int,
    capacity: int,  # kept in the signature for parity with ref; dropping
    *,                # is `slots >= capacity` downstream
    block_t: int = 256,
    interpret: bool = False,
):
    T, E = logits.shape
    block_t = min(block_t, T)
    nb = pl.cdiv(T, block_t)
    Tp = nb * block_t
    if Tp != T:
        logits = jnp.pad(logits, ((0, Tp - T), (0, 0)))

    kern = functools.partial(
        _router_kernel, k=k, block_t=block_t, total_t=T
    )
    ids, gates, slots = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_t, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, k), jnp.int32),
            jax.ShapeDtypeStruct((Tp, k), jnp.float32),
            jax.ShapeDtypeStruct((Tp, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, E), jnp.int32)],
        compiler_params=pltpu.compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(logits)
    return ids[:T], gates[:T], slots[:T]
