"""Flash-decode: one query token vs. a long KV cache (Pallas TPU).

The decode hot spot for ``decode_32k`` / ``long_500k``: each sequence reads
its whole KV cache once per step, so the kernel is HBM-bandwidth-bound.
The kernel reads the cache in its own ``(L, B, Smax, K, hd)`` layout: one
grid cell per (batch row, key block), each block ``(bk, K, hd)`` — every
KV head of ``bk`` positions, one contiguous read — at a layer index, so the
decode step hands it the whole stacked cache and nothing is sliced,
transposed or padded outside the kernel.  All ``H`` query heads of the row
score the block in one matrix product against its ``bk * K`` rows; a
block-diagonal mask keeps each head to its own KV head, and an
online-softmax running state streams the blocks, so the cache is read
exactly once.

The layer index and each row's valid cache length arrive via scalar
prefetch (SMEM): the layer picks the block, the length masks the tail —
so a continuous batch whose slots sit at different positions decodes in
one call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode"]

_NEG_INF = -1e30


def _decode_kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, scale: float, group: int,
                   ragged: bool):
    del layer_ref                                   # used by the index maps
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk, K, hd = k_ref.shape
    length = len_ref[pl.program_id(0)]
    q = q_ref[...].astype(jnp.float32)              # (H, hd)
    # row j of the flattened block is position j // K, KV head j % K
    k = k_ref[...].astype(jnp.float32).reshape(bk * K, hd)
    v = v_ref[...].astype(jnp.float32).reshape(bk * K, hd)
    if ragged:
        # the last block runs past Smax: what lies beyond is not the cache
        vpos = ki * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bk * K, 1), 0) // K
        v = jnp.where(vpos < length, v, 0.0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
    keep = (col % K == head) & (ki * bk + col // K < length)
    s = jnp.where(keep, s, _NEG_INF)

    m_prev = m_ref[...]                             # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                 length: jax.Array, layer: jax.Array | int = 0, *,
                 block_k: int = 512, interpret: bool = False) -> jax.Array:
    """q: (B, 1, H, hd); cache_k/v: a stacked (L, B, Smax, K, hd) cache read
    at ``layer``, or one layer's (B, Smax, K, hd); length: scalar or (B,)
    valid lengths.  Returns (B, 1, H, hd)."""
    B, one, H, hd = q.shape
    if cache_k.ndim == 4:                            # one layer: a bitcast
        cache_k, cache_v = cache_k[None], cache_v[None]
    Smax, K = cache_k.shape[2], cache_k.shape[3]
    bk = min(block_k, Smax)
    lengths = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(length, jnp.int32), (-1,)), (B,))
    layers = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    kv_spec = pl.BlockSpec((None, None, bk, K, hd),
                           lambda b, ki, lr, _: (lr[0], b, ki, 0, 0))
    row_spec = pl.BlockSpec((None, H, hd), lambda b, ki, *_: (b, 0, 0))
    kernel = functools.partial(_decode_kernel, scale=hd ** -0.5,
                               group=H // K, ragged=Smax % bk != 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, pl.cdiv(Smax, bk)),
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(layers, lengths, q.reshape(B, H, hd), cache_k, cache_v)
    return out.reshape(B, 1, H, hd)
