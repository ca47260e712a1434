"""Decoder-only language model covering dense / MoE / SSM / hybrid / VLM
families, built from repro.models.layers.

Layers are organized into *groups* — (sub-pattern, repeats) — so homogeneous
stacks compile as a single ``lax.scan`` over stacked parameters (compact HLO,
mandatory at 126 layers) while heterogeneous interleaves (jamba's 1:7
mamba:attn with MoE-every-2; kimi's leading dense layer) scan over periods
with the period body unrolled.

Forward signature is batch-dict based:
  * dense/moe/ssm/hybrid: {"tokens": (B,S) i32}
  * vlm ([vlm] stub):     {"embeds": (B,S,d), "positions": (B,S,3)}
(labels handled by the train-step, not the model).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..dist.context import shard_activations
from .config import ModelConfig
from . import layers as L

LayerSpec = Tuple[str, str]  # (mixer: attn|ssm, ffn: dense|moe)


# ---------------------------------------------------------------------------
# Layer grouping
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LayerGroup:
    subpattern: Tuple[LayerSpec, ...]
    repeats: int


def layer_pattern(cfg: ModelConfig) -> List[LayerSpec]:
    def ffn_kind(i: int) -> str:
        if cfg.is_moe_layer(i):
            return "moe"
        return "dense" if cfg.d_ff > 0 else "none"  # mamba2 blocks: mixer only

    return [
        ("attn" if cfg.is_attn_layer(i) else "ssm", ffn_kind(i))
        for i in range(cfg.num_layers)
    ]


def compute_groups(cfg: ModelConfig) -> List[LayerGroup]:
    pattern = layer_pattern(cfg)
    groups: List[LayerGroup] = []
    i = 0
    if cfg.first_dense_layers:
        groups.append(
            LayerGroup(tuple(pattern[: cfg.first_dense_layers]), repeats=1)
        )
        i = cfg.first_dense_layers
    body = pattern[i:]
    if not body:
        return groups
    period = 1
    if cfg.family == "hybrid" and cfg.attn_period:
        period = cfg.attn_period
    elif cfg.num_experts and cfg.moe_every > 1:
        period = cfg.moe_every
    # verify periodicity (construction guarantees it; assert for safety)
    assert len(body) % period == 0, (len(body), period)
    sub = tuple(body[:period])
    for r in range(len(body) // period):
        assert tuple(body[r * period : (r + 1) * period]) == sub
    groups.append(LayerGroup(sub, repeats=len(body) // period))
    return groups


# ---------------------------------------------------------------------------
# Block apply (one layer)
# ---------------------------------------------------------------------------
def block_apply(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict[str, Any],
    x: jnp.ndarray,
    positions: jnp.ndarray,
) -> jnp.ndarray:
    mixer, ffn = spec
    B, S, d = x.shape
    h = L.rms_norm(x, p["ln1"])
    if mixer == "attn":
        h = L.attention(p["attn"], h, cfg, positions, causal=True)
    else:
        h = L.mamba2_mixer(p["ssm"], h, cfg)
    x = shard_activations(x + h, "bsd")
    if ffn == "none":
        return x
    h2 = L.rms_norm(x, p["ln2"])
    if ffn == "moe":
        h2 = L.moe_ffn(p["moe"], h2.reshape(B * S, d), cfg).reshape(B, S, d)
    else:
        h2 = L.mlp(p["mlp"], h2, cfg.mlp_act)
    return shard_activations(x + h2, "bsd")


def block_decode(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict[str, Any],
    c: Dict[str, Any],
    x_t: jnp.ndarray,
    pos: jnp.ndarray,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    mixer, ffn = spec
    B = x_t.shape[0]
    h = L.rms_norm(x_t, p["ln1"])
    if mixer == "attn":
        h, c_new = L.attention_decode(p["attn"], h, c, pos, cfg)
    else:
        h, c_new = L.mamba2_decode(p["ssm"], h, c, cfg)
    x_t = x_t + h
    if ffn == "none":
        return x_t, c_new
    h2 = L.rms_norm(x_t, p["ln2"])
    if ffn == "moe":
        # serving is dropless: capacity-dropping a decode token silently
        # corrupts its output (training tolerates drops, inference must not)
        h2 = L.moe_ffn(p["moe"], h2.reshape(B, -1), cfg, dropless=True).reshape(B, 1, -1)
    else:
        h2 = L.mlp(p["mlp"], h2, cfg.mlp_act)
    return x_t + h2, c_new


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_block(key, cfg: ModelConfig, spec: LayerSpec) -> Dict[str, Any]:
    kmix, kffn = jax.random.split(key)
    p: Dict[str, Any] = {
        "ln1": jnp.ones((cfg.d_model,), L.pdt(cfg)),
        "ln2": jnp.ones((cfg.d_model,), L.pdt(cfg)),
    }
    mixer, ffn = spec
    if mixer == "attn":
        p["attn"] = L.init_attention(kmix, cfg)
    else:
        p["ssm"] = L.init_mamba2(kmix, cfg)
    if ffn == "moe":
        p["moe"] = L.init_moe(kffn, cfg)
    elif ffn == "dense":
        p["mlp"] = L.init_mlp(kffn, cfg)
    else:  # "none": mamba2 block has no separate FFN
        del p["ln2"]
    return p


def _stack(trees: List[Any]) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class LanguageModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.groups = compute_groups(cfg)

    # -- params ---------------------------------------------------------
    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(key, 3 + len(self.groups))
        params: Dict[str, Any] = {
            "embed": L._init(
                keys[0], (cfg.vocab_size, cfg.d_model), 0.02, L.pdt(cfg)
            ),
            "final_norm": jnp.ones((cfg.d_model,), L.pdt(cfg)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L._init(
                keys[1], (cfg.d_model, cfg.vocab_size), 0.02, L.pdt(cfg)
            )
        for gi, g in enumerate(self.groups):
            gkey = keys[3 + gi]
            reps = []
            for r in range(g.repeats):
                rkey = jax.random.fold_in(gkey, r)
                sub = [
                    _init_block(jax.random.fold_in(rkey, j), cfg, spec)
                    for j, spec in enumerate(g.subpattern)
                ]
                reps.append(sub)
            params[f"group{gi}"] = (
                _stack(reps) if g.repeats > 1 else reps[0]
            )
        return params

    # -- forward (train / prefill) -----------------------------------------
    def forward(
        self,
        params: Dict[str, Any],
        batch: Dict[str, Any],
        last_token_only: bool = False,
    ) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.family == "vlm" and "embeds" in batch:
            x = batch["embeds"].astype(L.cdt(cfg))
            B, S, _ = x.shape
            positions = batch.get("positions")
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        else:
            tokens = batch["tokens"]
            B, S = tokens.shape
            # gather f32 rows, then cast: the backward scatter-add of a
            # frequent token's rows then accumulates in f32, not bf16
            x = params["embed"][tokens].astype(L.cdt(cfg))
            positions = batch.get("positions")
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = shard_activations(x, "bsd")

        for gi, g in enumerate(self.groups):
            gp = params[f"group{gi}"]
            if g.repeats == 1:
                for j, spec in enumerate(g.subpattern):
                    x = block_apply(cfg, spec, gp[j], x, positions)
            else:
                def body(carry, rep_params, _g=g):
                    h = carry
                    for j, spec in enumerate(_g.subpattern):
                        h = block_apply(cfg, spec, rep_params[j], h, positions)
                    return h, None

                if cfg.remat == "block":
                    body = jax.checkpoint(body)
                x, _ = lax.scan(body, x, gp)
        x = L.rms_norm(x, params["final_norm"])
        if last_token_only:  # prefill: only the last position feeds sampling
            x = x[:, -1:, :]
        head = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        )
        logits = x @ head.astype(x.dtype)
        if cfg.logits_fp32:
            logits = logits.astype(jnp.float32)
        return logits

    # -- decode -------------------------------------------------------------
    def init_cache(
        self, batch_size: int, max_seq: int, dtype: Optional[Any] = None
    ) -> Dict[str, Any]:
        cfg = self.cfg
        dt = dtype or L.cdt(cfg)

        def one(spec: LayerSpec) -> Dict[str, Any]:
            if spec[0] == "attn":
                return {
                    "k": jnp.zeros(
                        (batch_size, max_seq, cfg.num_kv_heads, cfg.head_dim), dt
                    ),
                    "v": jnp.zeros(
                        (batch_size, max_seq, cfg.num_kv_heads, cfg.head_dim), dt
                    ),
                }
            conv_ch = cfg.ssm_d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            return {
                "h": jnp.zeros(
                    (batch_size, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
                    jnp.float32,
                ),
                "conv": jnp.zeros((batch_size, 3, conv_ch), dt),
            }

        cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
        for gi, g in enumerate(self.groups):
            if g.repeats == 1:
                cache[f"group{gi}"] = [one(spec) for spec in g.subpattern]
            else:
                cache[f"group{gi}"] = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (g.repeats,) + x.shape).copy()
                    if hasattr(x, "shape")
                    else x,
                    [one(spec) for spec in g.subpattern],
                )
        return cache

    def decode_step(
        self,
        params: Dict[str, Any],
        cache: Dict[str, Any],
        tokens: jnp.ndarray,  # (B,) int32 — the newest token per sequence
    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        pos = cache["pos"]
        x = params["embed"][tokens].astype(L.cdt(cfg))[:, None, :]  # (B,1,d)
        new_cache: Dict[str, Any] = {"pos": pos + 1}
        for gi, g in enumerate(self.groups):
            gp, gc = params[f"group{gi}"], cache[f"group{gi}"]
            if g.repeats == 1:
                new_list = []
                for j, spec in enumerate(g.subpattern):
                    x, c_new = block_decode(cfg, spec, gp[j], gc[j], x, pos)
                    new_list.append(c_new)
                new_cache[f"group{gi}"] = new_list
            else:
                def body(carry, pc, _g=g):
                    h = carry
                    rep_params, rep_cache = pc
                    outs = []
                    for j, spec in enumerate(_g.subpattern):
                        h, c_new = block_decode(
                            cfg, spec, rep_params[j], rep_cache[j], h, pos
                        )
                        outs.append(c_new)
                    return h, outs

                x, updated = lax.scan(body, x, (gp, gc))
                new_cache[f"group{gi}"] = updated
        x = L.rms_norm(x, params["final_norm"])
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head.astype(x.dtype))[:, 0]
        return logits.astype(jnp.float32), new_cache
