"""Elastic scaling: reshard any checkpoint onto a different mesh.

Checkpoints store logically-global arrays (mesh-agnostic); resharding is a
``device_put`` onto the new mesh's NamedShardings. Shrink (lost pod / fewer
hosts) and grow both reduce to the same operation — the training driver calls
``reshard_checkpoint`` at startup with whatever devices it finds.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import Mesh

from repro.launch.mesh import auto_mesh
from repro.models import transformer as T
from repro.models.partitioning import param_shardings
from repro.train import checkpoint as ckpt
from repro.train.optimizer import AdamWConfig, state_shapes
from repro.train.train_step import TrainConfig


def make_mesh_from_available(model_axis: int = 1) -> Mesh:
    """Build a (data, model) mesh from whatever devices exist right now."""
    devs = jax.devices()
    n = len(devs)
    assert n % model_axis == 0, (n, model_axis)
    return auto_mesh((n // model_axis, model_axis), ("data", "model"))


def reshard_checkpoint(
    ckpt_dir: str,
    step: int,
    cfg_model: T.ModelConfig,
    cfg_train: TrainConfig,
    mesh: Mesh,
    *,
    fsdp: bool = False,
) -> Tuple[object, object, dict]:
    """Load checkpoint ``step`` and place it on ``mesh`` (any device count)."""
    p_like = T.param_shapes(cfg_model)
    o_like = state_shapes(cfg_train.adamw, p_like)
    p_sh = param_shardings(cfg_model, p_like, mesh, fsdp=fsdp)
    o_sh = {
        "m": param_shardings(cfg_model, p_like, mesh, fsdp=fsdp),
        "v": param_shardings(cfg_model, p_like, mesh, fsdp=fsdp),
        "step": jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    }
    return ckpt.load(ckpt_dir, step, p_like, o_like, shardings=(p_sh, o_sh))
