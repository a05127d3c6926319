"""FlashAttention-2-style blocked causal GQA attention (Pallas TPU).

Layout: the wrapper transposes to head-major (B, H, S, hd) so each grid
cell streams one (bq x hd) query tile against (bk x hd) key/value tiles.
Online softmax state (running max / sum / accumulator) lives in VMEM
scratch; tile sizes are MXU-aligned multiples of 128 where the sequence
allows.  GQA maps query head h to kv head h // (H // K) in the index maps —
no materialized kv repetition.

Any query/key length is accepted: the wrapper pads both sequences up to a
whole number of tiles, the kernel masks the padded keys, and the causal
mask keeps the queries aligned to the *last* ``Sq`` of the ``Sk`` keys.
The backward pass recomputes through :func:`ref.attention_ref` (a
``custom_vjp``), so training differentiates through the kernel.

Validated against ``ref.attention_ref`` in interpret mode (CPU); the TPU
path is the deployment target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, bq: int, bk: int, seq_q: int,
                 seq_k: int):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)            # (bq, hd)
    k = k_ref[...].astype(jnp.float32)            # (bk, hd)
    v = v_ref[...].astype(jnp.float32)            # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        qi = pl.program_id(2)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
            + (seq_k - seq_q)
        s = jnp.where(kpos <= qpos, s, _NEG_INF)
    if seq_k % bk:                                # padded keys of the tail tile
        s = jnp.where(kpos < seq_k, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, None]).astype(o_ref.dtype)


def _forward(q, k, v, causal: bool, block_q: int, block_k: int,
             interpret: bool) -> jax.Array:
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    group = H // K
    scale = hd ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pq, pk = -Sq % bq, -Sk % bk                   # pad to whole tiles

    qt = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    grid = (B, H, (Sq + pq) // bq, (Sk + pk) // bk)
    kernel = functools.partial(_attn_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, seq_q=Sq, seq_k=Sk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # running sum
            pltpu.VMEM((bq, hd), jnp.float32),    # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :Sq]      # (B, Sq, H, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    return _forward(q, k, v, causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    return _forward(q, k, v, causal, block_q, block_k, interpret), (q, k, v)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(ref.attention_ref, causal=causal),
                     *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False
                    ) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Sk, K, hd) with H % K == 0."""
    return _flash(q, k, v, causal, block_q, block_k, interpret)
