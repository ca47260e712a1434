"""Production mesh construction.

Single-pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis carries
data parallelism over DCN (params replicated per pod by default; FSDP can
extend over ("pod","data") for the 1T-param configs — see ShardingPlan).

Defined as FUNCTIONS so importing this module never touches jax device
state; only launch/dryrun.py (which sets XLA_FLAGS first) builds the big
meshes.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from ..dist.context import ShardingPlan


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], devices: Optional[Sequence] = None
) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    jax 0.9 makes ``Explicit`` axes by default, and explicit sharding
    rejects the model's GSPMD-style gathers (an embedding lookup whose
    table and token ids both shard over "data").  The sharding rules and
    ``with mesh, use_plan(plan)`` are written for the compiler-propagated
    (``Auto``) mode.
    """
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(shape),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run via "
            "launch/dryrun.py which sets xla_force_host_platform_device_count"
        )
    return make_mesh(shape, axes, devices=devices[:n])


def make_plan(mesh: Mesh, *, fsdp_over_pod: bool = False,
              seq_shard: bool = False) -> ShardingPlan:
    multi = "pod" in mesh.axis_names
    data_axes = ("pod", "data") if multi else ("data",)
    fsdp = ("pod", "data") if (multi and fsdp_over_pod) else "data"
    return ShardingPlan(
        data_axes=data_axes,
        model_axis="model",
        fsdp_axis=fsdp,
        seq_axis="model" if seq_shard else None,
    )


def make_test_mesh(data: int = 1, model: int = 1) -> Optional[Mesh]:
    """Tiny mesh over however many devices exist (CPU tests)."""
    n = data * model
    if len(jax.devices()) < n:
        return None
    return make_mesh((data, model), ("data", "model"), devices=jax.devices()[:n])
