"""Cross-pod gradient compression (beyond-paper optimization).

On a multi-pod mesh the inter-pod links are the scarce resource (DCN or
long ICI hops vs. intra-pod ICI).  We compress the cross-pod portion of the
gradient all-reduce to int8 with per-tensor scales and error feedback:

  1. reduce gradients *within* the pod in full precision (fast links),
  2. quantize to int8 (+ carry the quantization error into the next step),
  3. exchange int8 across pods (4x fewer wire bytes than f32),
  4. dequantize and broadcast intra-pod.

Implemented with shard_map over the 'pod' axis: the int8 exchange is an
``all_to_all``-shard + local-sum + ``all_gather`` ring, so the bytes on the
pod axis really are int8.  Off in the paper-faithful baseline.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Any


def _quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum_pod(x: jax.Array, axis_name: str = "pod") -> jax.Array:
    """int8 all-reduce over `axis_name` (call inside shard_map/pjit-manual).

    reduce-scatter (all_to_all of int8 shards + local sum) then all-gather
    of the int8 result: every element crosses the pod links exactly twice
    as one byte instead of four.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    q, scale = _quantize(flat)
    # every pod needs every scale to dequantize partial sums consistently
    scales = lax.all_gather(scale, axis_name)                  # (n,)
    shards = q.reshape(n, -1)
    recv = lax.all_to_all(shards, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)                         # (n, chunk)
    # dequantize each pod's chunk with its own scale, sum locally
    part = jnp.sum(recv.astype(jnp.float32) * scales[:, None], axis=0)
    # re-quantize the partial sum and gather it from all pods
    q2, s2 = _quantize(part)
    all_s2 = lax.all_gather(s2, axis_name)                     # (n,)
    all_q2 = lax.all_gather(q2, axis_name)                     # (n, chunk)
    full = (all_q2.astype(jnp.float32) * all_s2[:, None]).reshape(-1)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape).astype(x.dtype)


def compress_grads_with_feedback(grads: Params, error: Optional[Params]
                                 ) -> Tuple[Params, Params]:
    """Per-tensor int8 quantization with error feedback (host-level API).

    Returns (quantized-dequantized grads, new error buffers). Used by the
    trainer when ``grad_compress`` is enabled but the mesh has no pod axis
    (single-pod: compression only changes numerics, not traffic — kept for
    parity testing)."""
    if error is None:
        error = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        q, scale = _quantize(corrected)
        deq = q.astype(jnp.float32) * scale
        return deq.astype(g.dtype), corrected - deq

    out = jax.tree.map(one, grads, error)
    deq = jax.tree.map(lambda t: t[0], out,
                       is_leaf=lambda t: isinstance(t, tuple))
    new_err = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda t: isinstance(t, tuple))
    return deq, new_err
