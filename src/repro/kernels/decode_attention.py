"""Flash-decode: one query token vs. a long KV cache (Pallas TPU).

The decode hot spot for ``decode_32k`` / ``long_500k``: each sequence reads
its whole KV cache once per step, so the kernel is HBM-bandwidth-bound.
We process one (batch, kv-head) pair per grid cell with all ``group``
query heads of that kv head together (a (group x hd) tile), streaming the
cache in ``block_k`` tiles with an online-softmax running state — so the
cache is read exactly once.

Each row's valid cache length arrives via scalar prefetch (SMEM, one
int32 per batch row, indexed by the batch program id) and masks the tail
tiles — so a continuous batch whose slots sit at different positions
decodes in one call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode"]

_NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, bk: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)              # (group, hd)
    k = k_ref[...].astype(jnp.float32)              # (bk, hd)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # mask positions beyond this row's valid cache length
    length = len_ref[pl.program_id(0)]
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < length, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                 length: jax.Array, *, block_k: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q: (B, 1, H, hd); cache_k/v: (B, Smax, K, hd); length: scalar or
    (B,) valid lengths.  Returns (B, 1, H, hd)."""
    B, one, H, hd = q.shape
    Smax, K = cache_k.shape[1], cache_k.shape[2]
    group = H // K
    bk = min(block_k, Smax)
    scale = hd ** -0.5

    qt = q.reshape(B, K, group, hd)                  # heads grouped by kv head
    kt = cache_k.transpose(0, 2, 1, 3)               # (B, K, Smax, hd)
    vt = cache_v.transpose(0, 2, 1, 3)
    pad = -Smax % bk                                 # masked by the lengths
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    lengths = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(length, jnp.int32), (-1,)), (B,))

    grid = (B, K, (Smax + pad) // bk)
    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, group, hd),
                             lambda b, h, ki, *_: (b, h, 0, 0)),
                pl.BlockSpec((None, None, bk, hd),
                             lambda b, h, ki, *_: (b, h, ki, 0)),
                pl.BlockSpec((None, None, bk, hd),
                             lambda b, h, ki, *_: (b, h, ki, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, group, hd),
                                   lambda b, h, ki, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group,), jnp.float32),
                pltpu.VMEM((group,), jnp.float32),
                pltpu.VMEM((group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qt, kt, vt)
    return out.reshape(B, 1, H, hd)
