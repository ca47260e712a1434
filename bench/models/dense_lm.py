"""Dense decoder-only LM (StarCoder2): weights, plain reference, FLOPs.

The architecture as the configuration file states it: pre-norm blocks of
RMSNorm → grouped-query attention with rotary positions (and a sliding
window where ``attn_window`` > 0) → RMSNorm → GELU MLP, a final RMSNorm and
an untied output head.  The weights are laid out as the system under test
takes them: the layers stacked along a leading axis in ``group0[0]``.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from .. import refops as R


def param_spec(m: Dict[str, Any]) -> Dict[str, Any]:
    """(shape, init scale) of every weight; scale None means ones."""
    L, d, ff, V = m["num_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    qd, kvd = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    if L < 2 or m.get("tie_embeddings") or m.get("qk_norm") or m["mlp_act"] != "gelu":
        raise ValueError("dense_lm covers stacked (>= 2) GELU layers, untied, no qk-norm")
    block = {
        "ln1": ((L, d), None),
        "ln2": ((L, d), None),
        "attn": {
            "wq": ((L, d, qd), 1 / math.sqrt(d)),
            "wk": ((L, d, kvd), 1 / math.sqrt(d)),
            "wv": ((L, d, kvd), 1 / math.sqrt(d)),
            "wo": ((L, qd, d), 1 / math.sqrt(qd)),
        },
        "mlp": {"w1": ((L, d, ff), 1 / math.sqrt(d)), "w2": ((L, ff, d), 1 / math.sqrt(ff))},
    }
    return {
        "embed": ((V, d), 0.02),
        "final_norm": ((d,), None),
        "lm_head": ((d, V), 0.02),
        "group0": [block],
    }


def input_spec(m: Dict[str, Any], batch: Dict[str, Any]) -> Dict[str, Any]:
    """Shape and dtype of each leaf of a batch."""
    rows, seq = batch["rows"], batch["seq_len"]
    return {"tokens": ((rows, seq), "int32"), "labels": ((rows, seq), "int32")}


def init_params(m: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    return R.init_leaves(key, param_spec(m))


def loss_sums(
    m: Dict[str, Any], params: Dict[str, Any], batch: Dict[str, Any], ein: R.Einsum
) -> Dict[str, jnp.ndarray]:
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    H, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        h = R.rms_norm(x, p["ln1"])
        q = R.rope(ein("bsd,de->bse", h, p["attn"]["wq"]).reshape(B, S, H, D), m["rope_theta"])
        k = R.rope(ein("bsd,de->bse", h, p["attn"]["wk"]).reshape(B, S, Hkv, D), m["rope_theta"])
        v = ein("bsd,de->bse", h, p["attn"]["wv"]).reshape(B, S, Hkv, D)
        a = R.attention(ein, q, k, v, causal=True, window=m["attn_window"])
        x = x + ein("bse,ed->bsd", a.reshape(B, S, H * D), p["attn"]["wo"])
        h = R.rms_norm(x, p["ln2"])
        x = x + ein("bsf,fd->bsd", R.gelu(ein("bsd,df->bsf", h, p["mlp"]["w1"])), p["mlp"]["w2"])
        return x, None

    x, _ = lax.scan(layer, x, params["group0"][0])
    x = R.rms_norm(x, params["final_norm"])
    return R.token_losses(ein, x.reshape(B * S, -1), params["lm_head"], labels.reshape(-1))


def flops_per_step(m: Dict[str, Any], batch: Dict[str, Any]) -> float:
    """Model FLOPs of one training step (forward and backward, 3× forward),
    recomputation not counted.  Matmuls: 6 × weights in products × tokens.
    Attention: 2 products of S × S' × (heads × head_dim) per layer, counted
    over the key positions the mask keeps (the causal half, or the window)."""
    L, d, ff, V = m["num_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    qd, kvd = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    B, S = batch["rows"], batch["seq_len"]
    per_layer = 2 * d * qd + 2 * d * kvd + 2 * d * ff
    matmul = 6.0 * (L * per_layer + d * V) * B * S
    win = m["attn_window"] or S
    kept = sum(min(i + 1, win) for i in range(S))  # key positions per query, summed
    attn = 3 * 2 * 2.0 * kept * qd * B * L
    return matmul + attn


def param_count(m: Dict[str, Any]) -> int:
    return sum(math.prod(s) for s, _ in jax.tree.leaves(
        param_spec(m), is_leaf=lambda s: isinstance(s, tuple) and isinstance(s[0], tuple)))
