"""Plain float32 building blocks of the benchmark's model references.

Nothing here imports the program.  Every matrix product goes through an
``einsum`` made by :func:`make_einsum`:

* ``"f32"`` — float32 operands at ``Precision.HIGHEST`` (six bf16 passes on
  a TPU, so a true float32 product);
* ``"fp8"`` — the control: both operands of every product, and the
  cotangent of its backward pass, rounded to float8 with a per-tensor scale
  (e4m3 forward, e5m2 backward, as fp8 training does), multiplied at
  ``HIGHEST``, and the product rounded to bfloat16, the activations' type.
  This is the precision one step below the configurations' bfloat16
  compute: the step a bf16 program takes to fp8 matmuls.

Attention and the loss are computed in blocks of query rows, each block
under ``jax.checkpoint``, so that a reference at the published widths fits
one chip beside the optimizer state.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PAD_ID = 0  # label id left out of the loss
Z_LOSS = 1e-4  # weight of log-sum-exp squared in the training loss

Einsum = Callable[[str, jnp.ndarray, jnp.ndarray], jnp.ndarray]


def _plain(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _round_fp8(x: jnp.ndarray, dtype: Any) -> jnp.ndarray:
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(x)) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _bf16(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    e4 = jnp.float8_e4m3fn
    return _bf16(_plain(spec, _round_fp8(a, e4), _round_fp8(b, e4)))


def _fp8_fwd(spec, a, b):
    return _fp8_einsum(spec, a, b), (a, b)


def _fp8_bwd(spec, res, g):
    a, b = res
    e4 = jnp.float8_e4m3fn
    _, vjp = jax.vjp(
        lambda x, y: _plain(spec, x, y), _round_fp8(a, e4), _round_fp8(b, e4)
    )
    return vjp(_round_fp8(g, jnp.float8_e5m2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def make_einsum(precision: str) -> Einsum:
    if precision == "f32":
        return _plain
    if precision == "fp8":
        return _fp8_einsum
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    """GELU, tanh form (StarCoder2's ``gelu_pytorch_tanh``, Whisper's gelu
    as the program computes it)."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding on (B, S, H, D), halves rotated (GPT-NeoX layout)."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sinusoid(seq: int, d: int) -> jnp.ndarray:
    """Sine at even channels, cosine at odd ones."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    return pe.at[:, 1::2].set(jnp.cos(pos * div))


def _row_block(n: int, target: int) -> int:
    """Largest divisor of ``n`` not above ``target``."""
    for b in range(min(n, target), 0, -1):
        if n % b == 0:
            return b
    return 1


def attention(
    ein: Einsum,
    q: jnp.ndarray,  # (B, S, H, D)
    k: jnp.ndarray,  # (B, T, Hkv, D)
    v: jnp.ndarray,
    *,
    causal: bool,
    window: int = 0,
    block: int = 512,
) -> jnp.ndarray:
    """Softmax attention with grouped KV heads, in blocks of query rows."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    q = q / math.sqrt(D)
    blk = _row_block(S, block)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def one(args):
        qb, start = args
        s = ein("bqhd,bkhd->bhqk", qb, k)
        qpos = start + jnp.arange(blk)
        mask = jnp.ones((blk, T), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ein("bhqk,bkhd->bqhd", p, v)

    qb = q.reshape(B, S // blk, blk, H, D).swapaxes(0, 1)
    out = lax.map(one, (qb, jnp.arange(S // blk) * blk))
    return out.swapaxes(0, 1).reshape(B, S, H, D)


def token_losses(
    ein: Einsum,
    x: jnp.ndarray,  # (N, d) final hidden states
    head: jnp.ndarray,  # (d, V)
    labels: jnp.ndarray,  # (N,)
    block: int = 1024,
) -> Dict[str, jnp.ndarray]:
    """Sums over tokens of the cross-entropy and of the z-loss term, with
    ``PAD_ID`` labels left out, and the count of tokens kept; in blocks."""
    N = x.shape[0]
    blk = _row_block(N, block)

    @jax.checkpoint
    def one(args):
        xb, lb = args
        logits = ein("nd,dv->nv", xb, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        mask = (lb != PAD_ID).astype(jnp.float32)
        return jnp.stack([((lse - gold) * mask).sum(), (Z_LOSS * lse**2 * mask).sum(), mask.sum()])

    parts = lax.map(one, (x.reshape(N // blk, blk, -1), labels.reshape(N // blk, blk)))
    nll, z, count = parts.sum(axis=0)
    return {"nll": nll, "z": z, "count": count}


def mean_loss(sums: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    denom = jnp.maximum(sums["count"], 1.0)
    return {"total": (sums["nll"] + sums["z"]) / denom, "loss": sums["nll"] / denom}


# ---------------------------------------------------------------------------
# AdamW with linear warm-up, cosine decay and global-norm clipping
# ---------------------------------------------------------------------------
def lr_at(opt: Dict[str, Any], step: jnp.ndarray) -> jnp.ndarray:
    step = step.astype(jnp.float32)
    warm = opt["warmup_steps"]
    prog = jnp.clip((step - warm) / max(1.0, opt["decay_steps"] - warm), 0.0, 1.0)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * jnp.where(step < warm, step / max(1.0, warm), cos)


def adamw_init(params: Any) -> Dict[str, Any]:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"step": jnp.zeros((), jnp.int32), "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params)}


def adamw_update(opt: Dict[str, Any], params: Any, grads: Any, state: Dict[str, Any]):
    """Decoupled weight decay on leaves of two or more dimensions."""
    step = state["step"] + 1
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    lr = lr_at(opt, step)
    b1, b2 = opt["b1"], opt["b2"]
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def one(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        delta = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        return p - lr * delta, m, v

    out = jax.tree.map(one, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)
    return pick(0), {"step": step, "m": pick(1), "v": pick(2)}


def leaf_norms(tree: Any) -> jnp.ndarray:
    """Float32 norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_key(key: jax.Array, path: Any) -> jax.Array:
    """A key of its own for each leaf, fixed by the leaf's path."""
    return jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path).encode()))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


def init_leaves(key: jax.Array, spec: Any) -> Any:
    """Weights from a tree of ``(shape, scale)``: a normal draw times
    ``scale``, or ones where ``scale`` is None."""
    def one(path, s):
        shape, scale = s
        if scale is None:
            return jnp.ones(shape, jnp.float32)
        return jax.random.normal(leaf_key(key, path), shape, jnp.float32) * scale

    return jax.tree_util.tree_map_with_path(
        one, spec, is_leaf=lambda s: isinstance(s, tuple) and len(s) == 2
        and isinstance(s[0], tuple)
    )
