"""repro.train — optimizer, train step, loss, checkpointing.

Training loops consume batches through ``DeviceFeeder`` (re-exported from
``repro.feed``): service fetch + host→device transfer run on a background
thread behind a double buffer, so the jitted step never blocks on input.
"""
from .optimizer import AdamWConfig, apply_updates, init_state, lr_schedule
from .step import (
    cross_entropy,
    init_train_state,
    make_eval_step,
    make_grad_fn,
    make_loss_fn,
    make_train_step,
)
from .checkpoint import latest_step, restore_checkpoint, save_checkpoint
from .compile_cache import use_compile_cache
from ..feed import DeviceFeeder, FeedMetrics

__all__ = [
    "AdamWConfig",
    "DeviceFeeder",
    "FeedMetrics",
    "apply_updates",
    "cross_entropy",
    "init_state",
    "init_train_state",
    "latest_step",
    "lr_schedule",
    "make_eval_step",
    "make_grad_fn",
    "make_loss_fn",
    "make_train_step",
    "restore_checkpoint",
    "save_checkpoint",
    "use_compile_cache",
]
