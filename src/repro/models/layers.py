"""Shared building blocks: norms, RoPE, GQA attention, SwiGLU, embeddings.

Everything is pure-functional: ``init_*`` builds parameter dicts,
``apply``-style functions consume them.  Attention routes through
``kernels.ops`` so the Pallas kernels (TPU target) and the jnp reference
(CPU validation / XLA fallback) share one call site.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .sharding import shard

__all__ = [
    "init_linear", "linear", "init_rmsnorm", "rms_norm", "init_embed",
    "embed_lookup", "rope_freqs", "apply_rope", "init_attention",
    "attention_block", "attention_decode", "attention_decode_at",
    "init_mlp", "mlp_block",
    "cross_entropy_loss",
]

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def _dense_init(key, shape, in_dim, dtype):
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32) -> Params:
    p = {"w": _dense_init(key, (d_in, d_out), d_in, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"w": jnp.ones((d,), dtype)}


def rms_norm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * p["w"].astype(jnp.float32)).astype(dt)


def init_embed(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed_lookup(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


# ---------------------------------------------------------------------------
# RoPE (half-rotation convention)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, positions: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for positions: (..., head_dim/2)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (S, hd/2) or (B, S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:                       # (S, half) -> (1, S, 1, half)
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    elif cos.ndim == 3:                     # (B, S, half) -> (B, S, 1, half)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)


def _rope_tables(seq: int, head_dim: int, theta: float,
                 offset: jax.Array | int = 0):
    pos = jnp.arange(seq) + offset
    return rope_freqs(head_dim, theta, pos)  # (S, half) each


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   *, qkv_bias: bool = False, qk_norm: bool = False,
                   dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": _dense_init(ks[0], (d_model, n_heads * head_dim), d_model, dtype),
        "wk": _dense_init(ks[1], (d_model, n_kv * head_dim), d_model, dtype),
        "wv": _dense_init(ks[2], (d_model, n_kv * head_dim), d_model, dtype),
        "wo": _dense_init(ks[3], (n_heads * head_dim, d_model),
                          n_heads * head_dim, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def _headwise_rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dt)


def _project_qkv(p: Params, x: jax.Array, n_heads: int, n_kv: int,
                 head_dim: int, theta: float, eps: float,
                 pos_offset: jax.Array | int = 0, mode: str = "train"):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv, head_dim)
    v = v.reshape(B, S, n_kv, head_dim)
    if "q_norm" in p:
        q = _headwise_rmsnorm(q, p["q_norm"], eps)
        k = _headwise_rmsnorm(k, p["k_norm"], eps)
    if theta > 0:
        cos, sin = _rope_tables(S, head_dim, theta, pos_offset)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    from .sharding import axis_size, current_rules, gqa_axes
    tp = current_rules().get("tp")
    n = axis_size(tp) if isinstance(tp, str) else 1
    if mode == "decode":
        # decode: hd-sharded q+cache when kv doesn't divide (gather-free,
        # small logits psum)
        kv_ax, hd_ax = gqa_axes(n_kv, head_dim)
        q = shard(q, "batch", None, "tp" if kv_ax else None, hd_ax)
        k = shard(k, "batch", None, kv_ax, hd_ax)
        v = shard(v, "batch", None, kv_ax, hd_ax)
    else:
        # train/prefill: head-sharded q (kv repeated inside the attention
        # impl when K doesn't divide) — never psum S^2 logits
        q = shard(q, "batch", None, "tp" if n > 1 and n_heads % n == 0
                  else None, None)
        kv_ok = n > 1 and n_kv % n == 0
        k = shard(k, "batch", None, "tp" if kv_ok else None, None)
        v = shard(v, "batch", None, "tp" if kv_ok else None, None)
    return q, k, v


def attention_block(p: Params, x: jax.Array, *, n_heads: int, n_kv: int,
                    head_dim: int, theta: float = 1e6, causal: bool = True,
                    eps: float = 1e-5,
                    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None
                    ) -> jax.Array:
    """Full-sequence attention (training / prefill).

    ``kv_override`` supplies encoder K/V for cross-attention (q from x).
    """
    from ..kernels import ops
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim,
                           0.0 if kv_override is not None else theta, eps)
    if kv_override is not None:
        k, v = kv_override
        causal = False
    o = ops.attention(q, k, v, causal=causal)          # (B, S, H, hd)
    o = o.reshape(B, S, n_heads * head_dim)
    return o @ p["wo"]


def attention_decode(p: Params, x: jax.Array, cache_k: jax.Array,
                     cache_v: jax.Array, index: jax.Array, *, n_heads: int,
                     n_kv: int, head_dim: int, theta: float = 1e6,
                     eps: float = 1e-5, seq_shard: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against one layer's KV cache.

    cache_k/v: (B, S_max, K, hd); index: current length — scalar int32 for
    lockstep batches, or (B,) for continuous batching (per-slot positions).
    Returns (out (B,1,D), new_cache_k, new_cache_v).
    """
    o, ck, cv = attention_decode_at(p, x, cache_k[None], cache_v[None], 0,
                                    index, n_heads=n_heads, n_kv=n_kv,
                                    head_dim=head_dim, theta=theta, eps=eps,
                                    seq_shard=seq_shard)
    return o, ck[0], cv[0]


def attention_decode_at(p: Params, x: jax.Array, cache_k: jax.Array,
                        cache_v: jax.Array, layer: jax.Array | int,
                        index: jax.Array, *, n_heads: int, n_kv: int,
                        head_dim: int, theta: float = 1e6, eps: float = 1e-5,
                        seq_shard: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`attention_decode` at ``layer`` of a stacked
    (L, B, S_max, K, hd) cache: the new token's K/V row is written in place
    and attention reads the stack at that layer, so no layer is sliced out
    or written back.  Returns (out (B,1,D), new_cache_k, new_cache_v).
    """
    from ..kernels import ops
    B, one, _ = x.shape
    per_slot = jnp.ndim(index) > 0
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim, theta, eps,
                           pos_offset=index[:, None] if per_slot else index,
                           mode="decode")
    if per_slot:
        b_idx = jnp.arange(B)
        cache_k = cache_k.at[layer, b_idx, index].set(
            k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[layer, b_idx, index].set(
            v[:, 0].astype(cache_v.dtype))
    else:
        at = (layer, 0, index, 0, 0)
        cache_k = lax.dynamic_update_slice(
            cache_k, k[None].astype(cache_k.dtype), at)
        cache_v = lax.dynamic_update_slice(
            cache_v, v[None].astype(cache_v.dtype), at)
    o = ops.decode_attention(q, cache_k, cache_v, index + 1, layer,
                             seq_shard=seq_shard)      # (B, 1, H, hd)
    o = o.reshape(B, one, n_heads * head_dim)
    return o @ p["wo"], cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d_model, d_ff), d_model, dtype),
        "w_up": _dense_init(ks[1], (d_model, d_ff), d_model, dtype),
        "w_down": _dense_init(ks[2], (d_ff, d_model), d_ff, dtype),
    }


def mlp_block(p: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shard(h, "batch", None, "tp")
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       z_loss: float = 0.0) -> jax.Array:
    """Mean token cross-entropy, fp32-stable. logits (B,S,V), labels (B,S)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    loss = lse - gold
    if z_loss > 0:
        loss = loss + z_loss * lse ** 2
    return jnp.mean(loss)


def chunked_lm_loss(x: jax.Array, w_out: jax.Array, labels: jax.Array,
                    chunk: int = 512) -> jax.Array:
    """CE over the vocab projection without ever materializing the full
    (B, S, V) logits in fp32: sequence chunks are projected, reduced and
    rematerialized in the backward pass.

    x: (B, S, D) final hidden; w_out: (D, V); labels: (B, S).
    """
    B, S, D = x.shape
    if S % chunk != 0 or S <= chunk:
        return cross_entropy_loss(x @ w_out, labels)
    nc = S // chunk
    xs = jnp.moveaxis(x.reshape(B, nc, chunk, D), 1, 0)       # (nc,B,c,D)
    ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)     # (nc,B,c)

    @jax.checkpoint   # bwd recomputes the chunk logits from (xc, w_out)
    def chunk_loss(xc, lc):
        logits = (xc @ w_out).astype(jnp.float32)             # (B,c,V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    def body(acc, xs_ls):
        xc, lc = xs_ls
        return acc + chunk_loss(xc, lc), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (B * S)
