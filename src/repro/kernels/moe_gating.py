"""Fused MoE router: softmax + iterative top-k + renormalize (Pallas TPU).

One pass over the (tokens x experts) logits in VMEM tiles: row softmax in
fp32, then k rounds of (max, argmax, mask) to extract the top-k experts —
for k=8, E=128 this keeps the whole row resident in VMEM/VREGs instead of
lax.top_k's generic sort, and fuses the renormalization.

E=128 is exactly one TPU lane tile; token tiles are sublane-aligned.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_gating"]


def _gating_kernel(logits_ref, w_ref, id_ref, *, k: int):
    logits = logits_ref[...].astype(jnp.float32)          # (bt, E)
    bt, E = logits.shape
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    probs = p / jnp.sum(p, axis=-1, keepdims=True)

    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)
    total = jnp.zeros((bt,), jnp.float32)
    for j in range(k):                                     # static unroll
        w = jnp.max(probs, axis=-1)
        idx = jnp.argmax(probs, axis=-1).astype(jnp.int32)
        w_ref[:, j] = w
        id_ref[:, j] = idx
        total = total + w
        probs = jnp.where(cols == idx[:, None], -1.0, probs)
    for j in range(k):
        w_ref[:, j] = w_ref[:, j] / total


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def moe_gating(logits: jax.Array, k: int, *, block_t: int = 256,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """logits: (T, E) -> (weights (T,k) f32, ids (T,k) i32)."""
    T, E = logits.shape
    bt = min(block_t, T)
    pad = -T % bt                     # whole token tiles; padded rows dropped
    logits = jnp.pad(logits, ((0, pad), (0, 0)))
    kernel = functools.partial(_gating_kernel, k=k)
    w, ids = pl.pallas_call(
        kernel,
        grid=((T + pad) // bt,),
        in_specs=[pl.BlockSpec((bt, E), lambda t: (t, 0))],
        out_specs=[pl.BlockSpec((bt, k), lambda t: (t, 0)),
                   pl.BlockSpec((bt, k), lambda t: (t, 0))],
        out_shape=[jax.ShapeDtypeStruct((T + pad, k), jnp.float32),
                   jax.ShapeDtypeStruct((T + pad, k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(logits)
    return w[:T], ids[:T]
