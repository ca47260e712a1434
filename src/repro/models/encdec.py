"""Encoder-decoder transformer (whisper-large-v3 backbone).

Per the assignment, the conv/mel frontend is a STUB: the model consumes
precomputed frame embeddings ``enc_embeds`` of shape (B, encoder_seq, d_model)
(``input_specs()`` supplies the ShapeDtypeStruct).  Encoder layers are
bidirectional; decoder layers are causal self-attention + cross-attention
over the encoder output.  Decode uses a self-attn KV ring plus precomputed
cross-attention K/V (computed once per sequence at prefill).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..dist.context import shard_activations
from .config import ModelConfig
from . import layers as L


def _init_enc_block(key, cfg: ModelConfig) -> Dict[str, Any]:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), L.pdt(cfg)),
        "ln2": jnp.ones((cfg.d_model,), L.pdt(cfg)),
        "attn": L.init_attention(k1, cfg),
        "mlp": L.init_mlp(k2, cfg),
    }


def _init_dec_block(key, cfg: ModelConfig) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": jnp.ones((cfg.d_model,), L.pdt(cfg)),
        "ln_x": jnp.ones((cfg.d_model,), L.pdt(cfg)),
        "ln2": jnp.ones((cfg.d_model,), L.pdt(cfg)),
        "attn": L.init_attention(k1, cfg),
        "xattn": L.init_attention(k2, cfg),
        "mlp": L.init_mlp(k3, cfg),
    }


def _sinusoid(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def _sinusoid_at(pos: jnp.ndarray, d: int) -> jnp.ndarray:
    """Positional embedding row for a dynamic position scalar."""
    div = jnp.exp(jnp.arange(0, d, 2, jnp.float32) * (-math.log(10000.0) / d))
    angle = pos.astype(jnp.float32) * div
    pe = jnp.zeros((d,), jnp.float32)
    pe = pe.at[0::2].set(jnp.sin(angle))
    pe = pe.at[1::2].set(jnp.cos(angle))
    return pe


class EncDecModel:
    def __init__(self, cfg: ModelConfig):
        assert cfg.family == "encdec"
        self.cfg = cfg

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        enc = [
            _init_enc_block(jax.random.fold_in(ks[0], i), cfg)
            for i in range(cfg.encoder_layers)
        ]
        dec = [
            _init_dec_block(jax.random.fold_in(ks[1], i), cfg)
            for i in range(cfg.num_layers)
        ]
        stack = lambda blocks: jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
        return {
            "embed": L._init(ks[2], (cfg.vocab_size, cfg.d_model), 0.02, L.pdt(cfg)),
            "enc": stack(enc),
            "dec": stack(dec),
            "enc_norm": jnp.ones((cfg.d_model,), L.pdt(cfg)),
            "final_norm": jnp.ones((cfg.d_model,), L.pdt(cfg)),
        }

    # -- encoder -----------------------------------------------------------
    def encode(self, params: Dict[str, Any], enc_embeds: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        B, S, d = enc_embeds.shape
        x = enc_embeds.astype(L.cdt(cfg)) + _sinusoid(S, d).astype(L.cdt(cfg))[None]
        x = shard_activations(x, "bsd")
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        def body(carry, p):
            h = carry
            a = L.attention(
                p["attn"], L.rms_norm(h, p["ln1"]), cfg, positions,
                causal=False, use_rope=False,
            )
            h = h + a
            h = h + L.mlp(p["mlp"], L.rms_norm(h, p["ln2"]), cfg.mlp_act)
            return shard_activations(h, "bsd"), None

        if cfg.remat == "block":
            body = jax.checkpoint(body)
        x, _ = lax.scan(body, x, params["enc"])
        return L.rms_norm(x, params["enc_norm"])

    # -- decoder (teacher-forced training / prefill) -------------------------
    def forward(
        self,
        params: Dict[str, Any],
        batch: Dict[str, Any],
        last_token_only: bool = False,
    ) -> jnp.ndarray:
        cfg = self.cfg
        enc_out = self.encode(params, batch["enc_embeds"])
        tokens = batch["tokens"]
        B, S = tokens.shape
        # gather f32 rows, then cast: the backward scatter-add of a frequent
        # token's rows then accumulates in f32, not bf16
        x = params["embed"][tokens].astype(L.cdt(cfg))
        x = x + _sinusoid(S, cfg.d_model).astype(x.dtype)[None]
        x = shard_activations(x, "bsd")
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        def body(carry, p):
            h = carry
            h = h + L.attention(
                p["attn"], L.rms_norm(h, p["ln1"]), cfg, positions,
                causal=True, use_rope=False,
            )
            h = h + L.attention(
                p["xattn"], L.rms_norm(h, p["ln_x"]), cfg, positions,
                causal=False, kv_x=enc_out, use_rope=False,
            )
            h = h + L.mlp(p["mlp"], L.rms_norm(h, p["ln2"]), cfg.mlp_act)
            return shard_activations(h, "bsd"), None

        if cfg.remat == "block":
            body = jax.checkpoint(body)
        x, _ = lax.scan(body, x, params["dec"])
        x = L.rms_norm(x, params["final_norm"])
        if last_token_only:
            x = x[:, -1:, :]
        logits = x @ params["embed"].T.astype(x.dtype)  # whisper ties embeddings
        return logits.astype(jnp.float32) if cfg.logits_fp32 else logits

    # -- decode -------------------------------------------------------------
    def init_cache(
        self, params: Dict[str, Any], batch_size: int, max_seq: int,
        enc_embeds: Optional[jnp.ndarray] = None,
    ) -> Dict[str, Any]:
        """Self-attn KV ring + precomputed cross-attn K/V from the encoder."""
        cfg = self.cfg
        dt = L.cdt(cfg)
        Ld = cfg.num_layers
        if enc_embeds is None:
            enc_out = jnp.zeros((batch_size, cfg.encoder_seq, cfg.d_model), dt)
        else:
            enc_out = self.encode(params, enc_embeds)

        def xkv(p):  # (Ld, ...) stacked xattn K/V
            k = jnp.einsum("bsd,ldk->lbsk", enc_out, p["xattn"]["wk"].astype(dt))
            v = jnp.einsum("bsd,ldk->lbsk", enc_out, p["xattn"]["wv"].astype(dt))
            S = enc_out.shape[1]
            k = k.reshape(Ld, batch_size, S, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(Ld, batch_size, S, cfg.num_kv_heads, cfg.head_dim)
            return k, v

        xk, xv = xkv(params["dec"])
        return {
            "pos": jnp.zeros((), jnp.int32),
            "k": jnp.zeros(
                (Ld, batch_size, max_seq, cfg.num_kv_heads, cfg.head_dim), dt
            ),
            "v": jnp.zeros(
                (Ld, batch_size, max_seq, cfg.num_kv_heads, cfg.head_dim), dt
            ),
            "xk": xk,
            "xv": xv,
        }

    def decode_step(
        self, params: Dict[str, Any], cache: Dict[str, Any], tokens: jnp.ndarray
    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        pos = cache["pos"]
        B = tokens.shape[0]
        x = params["embed"][tokens].astype(L.cdt(cfg))[:, None, :]
        x = x + _sinusoid_at(pos, cfg.d_model).astype(x.dtype)[None, None, :]

        def body(carry, inp):
            h = carry
            p, kc, vc, xk, xv = inp
            a, c_new = L.attention_decode(
                p["attn"], L.rms_norm(h, p["ln1"]), {"k": kc, "v": vc}, pos, cfg
            )
            h = h + a
            h = h + self._cross_decode(p["xattn"], L.rms_norm(h, p["ln_x"]), xk, xv)
            h = h + L.mlp(p["mlp"], L.rms_norm(h, p["ln2"]), cfg.mlp_act)
            return h, (c_new["k"], c_new["v"])

        x, (k_new, v_new) = lax.scan(
            body, x, (params["dec"], cache["k"], cache["v"], cache["xk"], cache["xv"])
        )
        x = L.rms_norm(x, params["final_norm"])
        logits = (x @ params["embed"].T.astype(x.dtype))[:, 0]
        new_cache = dict(cache)
        new_cache.update({"pos": pos + 1, "k": k_new, "v": v_new})
        return logits.astype(jnp.float32), new_cache

    def _cross_decode(self, p, x_t, xk, xv):
        cfg = self.cfg
        B = x_t.shape[0]
        q = (x_t @ p["wq"].astype(x_t.dtype)).reshape(
            B, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim
        )
        scale = 1.0 / math.sqrt(cfg.head_dim)
        qf = (q.astype(jnp.float32) * scale).astype(xk.dtype)
        s = jnp.einsum(
            "bhgd,bkhd->bhgk", qf, xk, preferred_element_type=jnp.float32
        )
        pvals = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum(
            "bhgk,bkhd->bhgd", pvals.astype(xv.dtype), xv,
            preferred_element_type=jnp.float32,
        ).astype(x_t.dtype)
        return out.reshape(B, 1, cfg.q_dim) @ p["wo"].astype(x_t.dtype)
