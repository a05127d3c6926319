"""Logical-axis sharding rules for all model families.

We annotate weights and activations with *logical* axes and map them onto
mesh axes at launch time.  The baseline recipe (DESIGN.md §5):

* ``batch``   -> ("pod", "data")     (DP over pods and the data axis)
* ``tp``      -> "model"             (Megatron tensor parallel)
* ``expert``  -> "model"             (expert parallel, MoE with E >= axis)
* ``fsdp``    -> "data"              (parameter/optimizer sharding, big archs)
* ``seq``     -> "data"              (sequence-sharded long-context caches)

Rules map to ``None`` when a mesh axis is absent (single-pod vs multi-pod) or
when a tensor dimension is not divisible by the axis size — XLA supports
uneven sharding, but even tiles keep collective cost analysis clean.
"""

from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["AxisRules", "set_rules", "current_rules", "shard", "logical_to_pspec",
           "param_pspecs", "DEFAULT_RULES", "FSDP_RULES", "axis_size"]

Logical = Optional[Union[str, Tuple[str, ...]]]

# logical axis name -> mesh axis (or tuple of mesh axes) or None
AxisRules = Dict[str, Any]

DEFAULT_RULES: AxisRules = {
    "batch": ("pod", "data"),
    "tp": "model",
    "expert": "model",
    "tp_ff": None,         # MoE inner-dim TP (used when E < model axis)
    "fsdp": None,          # off in the faithful baseline for small archs
    "seq": "data",
    "vocab": "model",
}

FSDP_RULES: AxisRules = dict(DEFAULT_RULES, fsdp="data")

_ACTIVE: AxisRules = {}


def set_rules(rules: AxisRules) -> None:
    global _ACTIVE
    _ACTIVE = dict(rules)


def current_rules() -> AxisRules:
    return _ACTIVE


@contextmanager
def use_rules(rules: AxisRules):
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = dict(rules)
    try:
        yield
    finally:
        _ACTIVE = prev


def _mesh_axes() -> Dict[str, int]:
    """Axis sizes of the mesh set by ``jax.set_mesh`` (empty if none).

    Axes already in Manual mode (inside a shard_map) are not available to
    with_sharding_constraint / auto partitioning — they are dropped."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return {k: v for k, v in mesh.shape.items() if k not in mesh.manual_axes}


def _resolve(logical: Logical, mesh_axes: Dict[str, int], dim: Optional[int]
             ) -> Any:
    """Map one logical axis to mesh axes, dropping unmapped/ill-fitting ones.

    When the full axis product does not divide the dimension, fall back to
    the longest *prefix* that does (batch=128 can't take pod*data*model=512
    but happily takes pod*data=32).
    """
    if logical is None:
        return None
    rule = _ACTIVE.get(logical, None) if isinstance(logical, str) else logical
    if rule is None:
        return None
    axes = (rule,) if isinstance(rule, str) else tuple(rule)
    live = [a for a in axes if a in mesh_axes]
    if not live:
        return None
    if dim is not None:
        best: list = []
        best_total = 1
        n = len(live)
        for i in range(n):           # best contiguous subsequence that
            for j in range(i + 1, n + 1):   # divides the dimension
                cand = live[i:j]
                total = int(np.prod([mesh_axes[a] for a in cand]))
                if total > 0 and dim % total == 0 and total > best_total:
                    best, best_total = cand, total
        live = best
        if not live:
            return None
    if len(live) == 1:
        return live[0]
    return tuple(live)


def logical_to_pspec(logical_axes: Sequence[Logical],
                     shape: Optional[Sequence[int]] = None) -> P:
    mesh_axes = _mesh_axes()
    dims = list(shape) if shape is not None else [None] * len(logical_axes)
    return P(*[_resolve(l, mesh_axes, d) for l, d in zip(logical_axes, dims)])


def shard(x: jax.Array, *logical_axes: Logical) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh_axes = _mesh_axes()
    if not mesh_axes or not _ACTIVE:
        return x
    spec = logical_to_pspec(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Weight sharding rules, by parameter path.
# ---------------------------------------------------------------------------

# (regex over '/'-joined path, logical axes per dim). First match wins.
# Paths have stacked-layer leading dims stripped (see param_pspecs).
_WEIGHT_RULES: Tuple[Tuple[str, Tuple[Logical, ...]], ...] = (
    # embeddings & heads
    (r"embed/table$", ("vocab", "fsdp")),
    (r"lm_head/w$", ("fsdp", "vocab")),
    # attention
    (r"(attn|xattn)/wq$", ("fsdp", "tp")),
    (r"(attn|xattn)/wk$", ("fsdp", "tp")),
    (r"(attn|xattn)/wv$", ("fsdp", "tp")),
    (r"(attn|xattn)/wo$", ("tp", "fsdp")),
    (r"(attn|xattn)/b[qkv]$", ("tp",)),
    (r"(attn|xattn)/(q_norm|k_norm)$", (None,)),
    # dense mlp
    (r"mlp/w_(gate|up)$", ("fsdp", "tp")),
    (r"mlp/w_down$", ("tp", "fsdp")),
    # MoE
    (r"moe/router$", ("fsdp", None)),
    (r"moe/w_(gate|up)$", ("expert", "fsdp", "tp_ff")),
    (r"moe/w_down$", ("expert", "tp_ff", "fsdp")),
    # Mamba2 / SSD
    (r"ssm/in_proj$", ("fsdp", "tp")),
    (r"ssm/out_proj$", ("tp", "fsdp")),
    (r"ssm/conv_w$", (None, "tp")),
    (r"ssm/(a_log|dt_bias|d_skip)$", ("tp",)),
    (r"ssm/norm$", ("tp",)),
    # xLSTM
    (r"(mlstm|slstm)/w_(up|qkv|gates|if)$", ("fsdp", "tp")),
    (r"(mlstm|slstm)/w_down$", ("tp", "fsdp")),
    (r"(mlstm|slstm)/r_gates$", (None, "tp", None)),
    (r"(mlstm|slstm)/conv_w$", (None, "tp")),
    (r"(mlstm|slstm)/(b_gates|gn)$", ("tp",)),
    # norms and everything 1-D
    (r"(norm|norm1|norm2|norm3|final_norm|ln)(/w|/b)?$", (None,)),
)


def _strip_stack(path: str, arr_ndim: int, rule_ndim: int) -> int:
    """Number of leading stacked dims (layer stacking adds one)."""
    return max(arr_ndim - rule_ndim, 0)


def param_pspecs(params: Any) -> Any:
    """PartitionSpec pytree for a parameter pytree, via path rules.

    Works under an active mesh context; call inside
    ``with jax.set_mesh(mesh):`` after :func:`set_rules`.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def spec_for(pathkeys, arr) -> P:
        path = "/".join(str(getattr(k, "key", k)) for k in pathkeys)
        for pattern, logical in _WEIGHT_RULES:
            if re.search(pattern, path):
                extra = _strip_stack(path, arr.ndim, len(logical))
                axes: Tuple[Logical, ...] = (None,) * extra + tuple(logical)
                return logical_to_pspec(axes, arr.shape)
        return logical_to_pspec((None,) * arr.ndim, arr.shape)

    flat_specs = {tuple(pk): spec_for(pk, a) for pk, a in flat}
    return jax.tree_util.tree_map_with_path(
        lambda pk, a: flat_specs[tuple(pk)], params)


def gqa_axes(n_kv: int, head_dim: int):
    """Where to put 'tp' for GQA tensors laid out (..., K, [G,] hd).

    Returns (kv_axis, hd_axis) logical names: shard the kv-head dim when it
    divides the model axis (attention fully local per head group), else
    shard head_dim on BOTH q and cache so the contraction is a local
    partial sum + small psum — never an all-gather of the cache.
    """
    tp = _ACTIVE.get("tp")
    sizes = _mesh_axes()
    n = sizes.get(tp, 1) if isinstance(tp, str) else 1
    if n <= 1:
        return None, None
    if n_kv % n == 0:
        return "tp", None
    if head_dim % n == 0:
        return None, "tp"
    return None, None


def axis_size(*mesh_axis_names: str) -> int:
    sizes = _mesh_axes()
    out = 1
    for a in mesh_axis_names:
        out *= sizes.get(a, 1)
    return out
