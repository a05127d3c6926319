"""Mesh construction + per-arch axis rules.

Every mesh is built by :func:`make_mesh` with ``Auto`` axis types: the
sharding rules place arrays with ``with_sharding_constraint``, which only
refers to Auto axes (``jax.make_mesh`` defaults to Explicit).  Meshes are
built by FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` *before* first jax init.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

from ..configs.base import ArchConfig
from ..models.sharding import AxisRules, DEFAULT_RULES

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh",
           "rules_for"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))


# FSDP threshold: params whose bf16 copy + fp32 moments cannot be
# model-axis-sharded alone into 16 GB HBM.
_FSDP_PARAM_THRESHOLD = 20_000_000_000
# Below this, 16-way tensor parallel costs more in per-layer activation
# gathers than it saves: run pure data parallel over the WHOLE mesh
# (batch over pod x data x model), replicate weights, one grad all-reduce.
_TP_PARAM_THRESHOLD = 1_500_000_000


def rules_for(cfg: ArchConfig, *, model_axis: int = 16,
              fsdp: Optional[bool] = None,
              seq_shard_cache: bool = False,
              force_tp: Optional[bool] = None) -> AxisRules:
    """Axis rules adapted to the architecture (DESIGN.md §5)."""
    from ..models.model import param_count
    rules = dict(DEFAULT_RULES)
    n_params = param_count(cfg)
    if fsdp is None:
        fsdp = n_params >= _FSDP_PARAM_THRESHOLD
    if fsdp:
        rules["fsdp"] = "data"
    use_tp = n_params >= _TP_PARAM_THRESHOLD if force_tp is None else force_tp
    if not use_tp:
        rules["tp"] = None
        rules["vocab"] = None
        rules["tp_ff"] = None
        rules["batch"] = ("pod", "data", "model")   # DP over the whole mesh
    if cfg.is_moe:
        if cfg.n_experts >= model_axis:
            rules["expert"] = "model"     # expert parallel
            rules["tp_ff"] = None
        else:
            rules["expert"] = None        # few big experts: TP inside expert
            rules["tp_ff"] = "model"
    if seq_shard_cache:
        rules["seq"] = "data"
    return rules
