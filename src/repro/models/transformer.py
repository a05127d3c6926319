"""Dense decoder-only transformer (qwen3 / phi4 / qwen2 / mistral-large /
chameleon / lidc-demo families).

Layers are stacked along a leading L dim and executed with ``lax.scan`` so
HLO size and compile time are independent of depth (88-layer dry-runs).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from . import layers as L
from .sharding import shard

Params = Dict[str, Any]


def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False)
    raise ValueError(f"unknown remat policy {remat}")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(cfg: ArchConfig, key, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.hd, qkv_bias=cfg.qkv_bias,
                                 qk_norm=cfg.qk_norm, dtype=dtype),
        "norm2": L.init_rmsnorm(cfg.d_model, dtype),
        "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def init(cfg: ArchConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ke, kb, kh = jax.random.split(key, 3)
    block_keys = jax.random.split(kb, cfg.n_layers)
    params: Params = {
        "embed": L.init_embed(ke, cfg.vocab, cfg.d_model, dtype),
        "blocks": jax.vmap(lambda k: init_block(cfg, k, dtype))(block_keys),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": L._dense_init(kh, (cfg.d_model, cfg.vocab),
                                                cfg.d_model, dtype)}
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block_fwd(cfg: ArchConfig, x: jax.Array, blk: Params) -> jax.Array:
    h = L.rms_norm(blk["norm1"], x, cfg.norm_eps)
    x = x + L.attention_block(blk["attn"], h, n_heads=cfg.n_heads,
                              n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                              theta=cfg.rope_theta, eps=cfg.norm_eps)
    h = L.rms_norm(blk["norm2"], x, cfg.norm_eps)
    x = x + L.mlp_block(blk["mlp"], h)
    return shard(x, "batch", None, None)


def out_proj(cfg: ArchConfig, params: Params) -> jax.Array:
    return (params["embed"]["table"].T if cfg.tie_embeddings
            else params["lm_head"]["w"])


def logits_of(cfg: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x @ out_proj(cfg, params)


def lm_loss(cfg: ArchConfig, params: Params, x: jax.Array,
            labels: jax.Array) -> jax.Array:
    """Final norm + chunked CE (never materializes (B,S,V) fp32)."""
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return L.chunked_lm_loss(x, out_proj(cfg, params), labels)


def hidden(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
           remat: str = "none") -> jax.Array:
    x = L.embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", None, None)
    body = _remat_wrap(lambda h, blk: (_block_fwd(cfg, h, blk), None), remat)
    x, _ = lax.scan(body, x, params["blocks"])
    return x


def apply(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
          remat: str = "none") -> jax.Array:
    """Full forward: tokens (B, S) -> logits (B, S, V)."""
    return logits_of(cfg, params, hidden(cfg, params, tokens, remat=remat))


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array], *,
            remat: str = "none") -> jax.Array:
    x = hidden(cfg, params, batch["tokens"], remat=remat)
    return lm_loss(cfg, params, x, batch["labels"])


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "index": jnp.zeros((), jnp.int32)}


def prefill(cfg: ArchConfig, params: Params, tokens: jax.Array,
            max_seq: Optional[int] = None) -> Tuple[jax.Array, Params]:
    """Run the prompt, returning last-position logits and a filled cache."""
    B, S = tokens.shape
    max_seq = max_seq or S
    x = L.embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", None, None)

    def body(h, blk):
        hn = L.rms_norm(blk["norm1"], h, cfg.norm_eps)
        q, k, v = L._project_qkv(blk["attn"], hn, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.hd, cfg.rope_theta, cfg.norm_eps)
        from ..kernels import ops
        o = ops.attention(q, k, v, causal=True)
        o = o.reshape(B, S, cfg.n_heads * cfg.hd) @ blk["attn"]["wo"]
        h = h + o
        hn = L.rms_norm(blk["norm2"], h, cfg.norm_eps)
        h = h + L.mlp_block(blk["mlp"], hn)
        return shard(h, "batch", None, None), (k, v)

    x, (ks, vs) = lax.scan(body, x, params["blocks"])
    pad = max_seq - S
    if pad > 0:
        zeros = jnp.zeros((cfg.n_layers, B, pad, cfg.n_kv_heads, cfg.hd),
                          ks.dtype)
        ks = jnp.concatenate([ks, zeros], axis=2)
        vs = jnp.concatenate([vs, zeros], axis=2)
    cache = {"k": shard(ks, None, "batch", None, "tp", None),
             "v": shard(vs, None, "batch", None, "tp", None),
             "index": jnp.asarray(S, jnp.int32)}
    logits = logits_of(cfg, params, x[:, -1:])
    return logits, cache


def decode_step(cfg: ArchConfig, params: Params, cache: Params,
                tokens: jax.Array) -> Tuple[jax.Array, Params]:
    """One decode step. tokens (B, 1) -> logits (B, 1, V), updated cache."""
    B = tokens.shape[0]
    index = cache["index"]
    x = L.embed_lookup(params["embed"], tokens)

    from .sharding import current_rules
    zero_decode = bool(current_rules().get("fsdp"))

    def body(carry, xs):
        # the whole stacked cache is carried: each layer writes its new row
        # in place and attention reads the stack at that layer
        h, ck, cv = carry
        blk, layer = xs
        # ZeRO-sharded decode: keep the tiny activation sharded on D over
        # 'fsdp' so projections contract against *local* weight shards
        # (activation psums, bytes ~B*D) instead of all-gathering each
        # layer's weights (bytes ~D*F). The batch dim yields its axis —
        # resharding a (B,1,D) activation is ~free next to a weight gather.
        if zero_decode:
            h = shard(h, None, None, "fsdp")
        hn = L.rms_norm(blk["norm1"], h, cfg.norm_eps)
        o, ck, cv = L.attention_decode_at(blk["attn"], hn, ck, cv, layer,
                                          index, n_heads=cfg.n_heads,
                                          n_kv=cfg.n_kv_heads,
                                          head_dim=cfg.hd,
                                          theta=cfg.rope_theta,
                                          eps=cfg.norm_eps)
        h = h + o
        hn = L.rms_norm(blk["norm2"], h, cfg.norm_eps)
        if zero_decode:
            hn = shard(hn, None, None, "fsdp")
        h = h + L.mlp_block(blk["mlp"], hn)
        return (h, ck, cv), None

    (x, ks, vs), _ = lax.scan(body, (x, cache["k"], cache["v"]),
                              (params["blocks"], jnp.arange(cfg.n_layers)))
    logits = logits_of(cfg, params, x)
    new_cache = {"k": ks, "v": vs, "index": index + 1}
    return logits, new_cache
