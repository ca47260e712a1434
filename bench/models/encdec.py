"""Encoder-decoder transformer (Whisper): weights, plain reference, FLOPs.

As the configuration file states it: the encoder takes ``enc_embeds``
(B, encoder_seq, d_model) — what the conv front end would give — adds
sinusoidal positions and runs pre-norm blocks of bidirectional attention
and a GELU MLP, then a final RMSNorm.  The decoder embeds tokens, adds
sinusoidal positions, and runs blocks of causal self-attention,
cross-attention over the encoder output and a GELU MLP, then a final
RMSNorm and the head tied to the embedding.  Norms are RMSNorm without
bias, as the system under test computes them.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from .. import refops as R


def _attn_spec(L: int, d: int, qd: int, kvd: int) -> Dict[str, Any]:
    return {
        "wq": ((L, d, qd), 1 / math.sqrt(d)),
        "wk": ((L, d, kvd), 1 / math.sqrt(d)),
        "wv": ((L, d, kvd), 1 / math.sqrt(d)),
        "wo": ((L, qd, d), 1 / math.sqrt(qd)),
    }


def param_spec(m: Dict[str, Any]) -> Dict[str, Any]:
    Le, Ld, d, ff = m["encoder_layers"], m["num_layers"], m["d_model"], m["d_ff"]
    qd, kvd = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    if not m.get("tie_embeddings") or m["mlp_act"] != "gelu" or m.get("qk_norm"):
        raise ValueError("encdec covers tied-head GELU blocks without qk-norm")
    mlp = lambda L: {"w1": ((L, d, ff), 1 / math.sqrt(d)), "w2": ((L, ff, d), 1 / math.sqrt(ff))}
    return {
        "embed": ((m["vocab_size"], d), 0.02),
        "enc": {"ln1": ((Le, d), None), "ln2": ((Le, d), None),
                "attn": _attn_spec(Le, d, qd, kvd), "mlp": mlp(Le)},
        "dec": {"ln1": ((Ld, d), None), "ln_x": ((Ld, d), None), "ln2": ((Ld, d), None),
                "attn": _attn_spec(Ld, d, qd, kvd), "xattn": _attn_spec(Ld, d, qd, kvd),
                "mlp": mlp(Ld)},
        "enc_norm": ((d,), None),
        "final_norm": ((d,), None),
    }


def input_spec(m: Dict[str, Any], batch: Dict[str, Any]) -> Dict[str, Any]:
    """Shape and dtype of each leaf of a batch (the frame format carries
    float32, not bfloat16, so ``enc_embeds`` arrive in float32)."""
    rows, seq = batch["rows"], batch["seq_len"]
    return {"enc_embeds": ((rows, m["encoder_seq"], m["d_model"]), "float32"),
            "tokens": ((rows, seq), "int32"), "labels": ((rows, seq), "int32")}


def init_params(m: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    return R.init_leaves(key, param_spec(m))


def loss_sums(
    m: Dict[str, Any], params: Dict[str, Any], batch: Dict[str, Any], ein: R.Einsum
) -> Dict[str, jnp.ndarray]:
    H, Hkv, D, d = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["d_model"]
    enc = batch["enc_embeds"].astype(jnp.float32)
    tokens, labels = batch["tokens"], batch["labels"]
    B, T = enc.shape[:2]
    S = tokens.shape[1]

    def attend(p, x, src, causal):
        q = ein("bsd,de->bse", x, p["wq"]).reshape(B, x.shape[1], H, D)
        k = ein("bsd,de->bse", src, p["wk"]).reshape(B, src.shape[1], Hkv, D)
        v = ein("bsd,de->bse", src, p["wv"]).reshape(B, src.shape[1], Hkv, D)
        a = R.attention(ein, q, k, v, causal=causal)
        return ein("bse,ed->bsd", a.reshape(B, x.shape[1], H * D), p["wo"])

    def mlp(p, x):
        return ein("bsf,fd->bsd", R.gelu(ein("bsd,df->bsf", x, p["w1"])), p["w2"])

    @jax.checkpoint
    def enc_layer(x, p):
        h = R.rms_norm(x, p["ln1"])
        x = x + attend(p["attn"], h, h, False)
        return x + mlp(p["mlp"], R.rms_norm(x, p["ln2"])), None

    x, _ = lax.scan(enc_layer, enc + R.sinusoid(T, d)[None], params["enc"])
    enc_out = R.rms_norm(x, params["enc_norm"])

    @jax.checkpoint
    def dec_layer(x, p):
        h = R.rms_norm(x, p["ln1"])
        x = x + attend(p["attn"], h, h, True)
        x = x + attend(p["xattn"], R.rms_norm(x, p["ln_x"]), enc_out, False)
        return x + mlp(p["mlp"], R.rms_norm(x, p["ln2"])), None

    x = params["embed"][tokens] + R.sinusoid(S, d)[None]
    x, _ = lax.scan(dec_layer, x, params["dec"])
    x = R.rms_norm(x, params["final_norm"])
    return R.token_losses(ein, x.reshape(B * S, -1), params["embed"].T, labels.reshape(-1))


def flops_per_step(m: Dict[str, Any], batch: Dict[str, Any]) -> float:
    """Model FLOPs of one training step (3× forward), recomputation not
    counted.  Matmuls: 6 × weights × the positions they are applied to
    (encoder frames, decoder tokens; cross-attention K/V projections over
    encoder frames).  Attention: encoder and cross-attention in full, decoder
    self-attention at its causal half."""
    Le, Ld, d, ff, V = (m["encoder_layers"], m["num_layers"], m["d_model"], m["d_ff"],
                        m["vocab_size"])
    qd, kvd = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    B, T, S = batch["rows"], m["encoder_seq"], batch["seq_len"]
    attn_w = 2 * d * qd + 2 * d * kvd
    enc_mm = 6.0 * Le * (attn_w + 2 * d * ff) * B * T
    dec_mm = 6.0 * (Ld * (attn_w + 2 * d * qd + 2 * d * ff) + d * V) * B * S
    xkv_mm = 6.0 * Ld * (2 * d * kvd) * B * T
    enc_attn = 3 * 4.0 * T * T * qd * B * Le
    dec_attn = 3 * (2 * 2.0 * (S * (S + 1) // 2) * qd + 4.0 * S * T * qd) * B * Ld
    return enc_mm + dec_mm + xkv_mm + enc_attn + dec_attn


def param_count(m: Dict[str, Any]) -> int:
    return sum(math.prod(s) for s, _ in jax.tree.leaves(
        param_spec(m), is_leaf=lambda s: isinstance(s, tuple) and isinstance(s[0], tuple)))
