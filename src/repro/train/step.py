"""Train-step factory: loss + grad + AdamW update, microbatch accumulation,
and (pod, data, model) mesh sharding hooks.

``make_train_step(model, opt_cfg)`` returns a pure ``step(state, batch)``
suitable for ``jax.jit`` with explicit in/out shardings (see launch/dryrun).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models import Model
from . import optimizer as opt

PAD_ID = 0  # label id treated as padding (masked out of the loss)


def cross_entropy(
    logits: jnp.ndarray,  # (B, S, V) f32
    labels: jnp.ndarray,  # (B, S) i32
    z_loss: float = 1e-4,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    mask = (labels != PAD_ID).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    zl = z_loss * jnp.square(lse) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll + zl).sum() / denom
    acc = ((jnp.argmax(logits, -1) == labels) * mask).sum() / denom
    return loss, {"loss": nll.sum() / denom, "z_loss": zl.sum() / denom, "accuracy": acc}


def make_loss_fn(model: Model) -> Callable:
    def loss_fn(params: Any, batch: Dict[str, Any]) -> Tuple[jnp.ndarray, Dict]:
        logits = model.forward(params, batch)
        return cross_entropy(logits, batch["labels"])

    return loss_fn


def make_grad_fn(model: Model, microbatches: int = 1) -> Callable:
    """Returns grads(params, batch) -> (loss, aux, grads).

    ``microbatches > 1`` accumulates gradients over batch slices in f32
    (pipeline-friendly; also shrinks activation memory for the biggest
    configs).
    """
    grad_fn = jax.value_and_grad(make_loss_fn(model), has_aux=True)

    def grads_of(params: Any, batch: Dict[str, Any]):
        if microbatches <= 1:
            (loss, aux), grads = grad_fn(params, batch)
            return loss, aux, grads

        def slice_batch(i):
            return jax.tree.map(
                lambda x: x.reshape((microbatches, -1) + x.shape[1:])[i], batch
            )

        def acc_body(carry, i):
            g_acc, l_acc = carry
            (l, _aux), g = grad_fn(params, slice_batch(i))
            g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
            return (g_acc, l_acc + l), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), _ = lax.scan(
            acc_body, (g0, jnp.zeros((), jnp.float32)), jnp.arange(microbatches)
        )
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        loss = loss_sum / microbatches
        aux = {"loss": loss, "z_loss": jnp.zeros(()), "accuracy": jnp.zeros(())}
        return loss, aux, grads

    return grads_of


def make_train_step(
    model: Model,
    opt_cfg: Optional[opt.AdamWConfig] = None,
    microbatches: int = 1,
) -> Callable:
    """Returns step(train_state, batch) -> (train_state, metrics).

    train_state = {"params", "opt"}; gradients as ``make_grad_fn``.
    """
    opt_cfg = opt_cfg or opt.AdamWConfig()
    grads_of = make_grad_fn(model, microbatches)

    def step(state: Dict[str, Any], batch: Dict[str, Any]):
        params = state["params"]
        loss, aux, grads = grads_of(params, batch)
        new_params, new_opt, om = opt.apply_updates(params, grads, state["opt"], opt_cfg)
        metrics = {**aux, **om, "total_loss": loss}
        return {"params": new_params, "opt": new_opt}, metrics

    return step


def make_eval_step(model: Model) -> Callable:
    loss_fn = make_loss_fn(model)

    def step(params: Any, batch: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
        _, aux = loss_fn(params, batch)
        return aux

    return step


def init_train_state(
    model: Model, rng: jax.Array, opt_cfg: Optional[opt.AdamWConfig] = None
) -> Dict[str, Any]:
    params = model.init(rng)
    return {"params": params, "opt": opt.init_state(params, opt_cfg or opt.AdamWConfig())}
