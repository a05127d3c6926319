"""Named checkpoints in the data lake — the heart of LIDC fault tolerance.

Checkpoints are ordinary named data-lake objects::

    /lidc/data/ckpt/<run>/step=<N>        (json manifest of the state tree)
    /lidc/data/ckpt/<run>/step=<N>/leaf=i (each leaf's raw bytes, own dtype)
    /lidc/data/ckpt/<run>/latest          (json pointer {step, run})

Because the name is derived from the *job*, not the cluster, any cluster
that receives a retransmitted compute Interest can resume the work — the
location independence the paper claims for data, extended to training
state.  Restore re-shards onto whatever mesh the resuming cluster has
(elastic: the checkpoint stores global arrays, placement is per-cluster).
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..core.names import DATA_PREFIX, Name

__all__ = ["ckpt_prefix", "save_checkpoint", "restore_checkpoint",
           "latest_step"]

Params = Any


def ckpt_prefix(run: str) -> Name:
    return Name.parse(DATA_PREFIX).append("ckpt", run)


def _key(pathkeys) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in pathkeys)


def _flatten(state: Params) -> Dict[str, np.ndarray]:
    """The state's leaves on the host, in their own dtypes, keyed by path.
    One ``device_get`` of the whole tree puts every transfer in flight
    together."""
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(state))[0]
    return {_key(pathkeys): np.asarray(a) for pathkeys, a in flat}


def save_checkpoint(lake, run: str, step: int, state: Params,
                    meta: Optional[Dict[str, Any]] = None) -> Name:
    """Write the full state tree + advance the 'latest' pointer atomically
    (object first, pointer second — a torn write leaves the old pointer).
    Returns once every leaf, the manifest and the pointer are stored."""
    name = ckpt_prefix(run).append(f"step={step}")
    with tracing.span("ckpt.save", step=step) as rec:
        with tracing.span("ckpt.device_get"):
            arrays = _flatten(state)
        if rec is not None:
            rec["bytes"] = sum(a.nbytes for a in arrays.values())
        copies = getattr(lake.store, "copies", None)   # MemoryStore only
        with tracing.span("lake.put") as put:
            lake.put_arrays(name, arrays)
            lake.put_json(ckpt_prefix(run).append("latest"),
                          {"step": step, "run": run, **(meta or {})})
        if put is not None:
            put["store_copies"] = (None if copies is None
                                   else lake.store.copies - copies)
    return name


def latest_step(lake, run: str) -> Optional[int]:
    ptr = lake.get_json(ckpt_prefix(run).append("latest"))
    return None if ptr is None else int(ptr["step"])


def restore_checkpoint(lake, run: str, template: Params,
                       step: Optional[int] = None,
                       sharding=None) -> Tuple[Params, int]:
    """Restore into the structure of ``template`` (eval_shape tree ok).

    ``sharding``: optional pytree (or single sharding) to place restored
    arrays — this is where elastic re-sharding onto a different mesh
    happens."""
    if step is None:
        step = latest_step(lake, run)
        if step is None:
            raise FileNotFoundError(f"no checkpoint for run {run!r}")
    arrays = lake.get_arrays(ckpt_prefix(run).append(f"step={step}"))
    if arrays is None:
        raise FileNotFoundError(f"checkpoint step {step} missing for {run!r}")

    flat_t = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for pathkeys, tmpl in flat_t[0]:
        key = _key(pathkeys)
        if key not in arrays:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = arrays[key]
        assert tuple(arr.shape) == tuple(tmpl.shape), (key, arr.shape,
                                                       tmpl.shape)
        val = jnp.asarray(arr, dtype=tmpl.dtype)
        leaves.append(val)
    state = jax.tree_util.tree_unflatten(flat_t[1], leaves)
    if sharding is not None:
        if jax.tree_util.tree_structure(sharding, is_leaf=lambda x: x is None) \
                == jax.tree_util.tree_structure(state):
            state = jax.tree.map(jax.device_put, state, sharding)
        else:
            state = jax.tree.map(lambda x: jax.device_put(x, sharding), state)
    return state, step
