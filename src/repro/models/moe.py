"""Mixture-of-Experts transformer (qwen3-moe-30b-a3b, grok-1-314b).

Dispatch is **sort-based** (dropless up to a capacity factor), not the
GShard one-hot einsum: the (T, E, C) dispatch tensor at 1M tokens x 128
experts would dominate HBM.  Sorting tokens by expert id and scattering
into an (E, C, d) buffer keeps the working set at O(T·d + E·C·d) and lowers
to gather/scatter + batched matmul, which the SPMD partitioner turns into
expert-parallel all-to-all style exchanges when E is sharded over 'model'.

When n_experts < model-axis size (grok-1: 8e over 16 ways) expert weights
are instead tensor-parallel over d_ff ('tp_ff' logical axis) — set in the
launch-time axis rules.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from . import layers as L
from . import transformer as T
from .sharding import shard

Params = Dict[str, Any]


def init_moe(cfg: ArchConfig, key, dtype) -> Params:
    kr, kg, ku, kd = jax.random.split(key, 4)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff

    def expert_w(k, shape, fan_in):
        return (jax.random.normal(k, shape) / jnp.sqrt(fan_in)).astype(dtype)

    return {
        "router": L._dense_init(kr, (D, E), D, jnp.float32),  # fp32 routing
        "w_gate": expert_w(kg, (E, D, F), D),
        "w_up": expert_w(ku, (E, D, F), D),
        "w_down": expert_w(kd, (E, F, D), F),
    }


def init_block(cfg: ArchConfig, key, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.hd, qkv_bias=cfg.qkv_bias,
                                 qk_norm=cfg.qk_norm, dtype=dtype),
        "norm2": L.init_rmsnorm(cfg.d_model, dtype),
        "moe": init_moe(cfg, k2, dtype),
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
# expert-parallel dispatch (shard_map)
#
# Activations are replicated over the 'model' axis (TP convention between
# matmuls), so MoE dispatch needs NO all-to-all at all: every model column
# routes the same tokens, keeps only the assignments that hit ITS local
# expert slice (EP mode, E >= axis) or computes all experts on its d_ff
# slice (TP mode, E < axis), and one psum over 'model' — the same
# collective Megatron TP pays for a dense MLP — combines the columns.
# Dynamic scatters stay device-local, which is what makes this lower
# without the partitioner replicating the token stream.
# ---------------------------------------------------------------------------

def _local_moe(cfg: ArchConfig, xf: jax.Array, p: Params, e_lo, E_loc: int
               ) -> jax.Array:
    """Sort-based dispatch of local tokens into local experts.

    xf: (T, D) local tokens; expert weights in p are the local slice
    (E_loc, D, F_loc).  Returns this column's partial output (T, D).

    Every (token x D) gather/scatter operates on the SELECTED assignments
    only — positions are computed pre-sort (cheap (Tk, E_loc) cumsum) so
    the sorted stream can be statically sliced to E_loc*cap entries
    (~E_loc*cap/Tk of the naive dispatch traffic; 12.8x for qwen3-moe).
    """
    from ..kernels import ops
    T, D = xf.shape
    E, k = cfg.n_experts, cfg.top_k
    Tk = T * k
    cap = int(cfg.capacity_factor * Tk / E)
    cap = max(8, (cap + 7) // 8 * 8)
    n_sel = min(E_loc * cap, Tk)

    logits = xf.astype(jnp.float32) @ p["router"]             # (T, E)
    weights, ids = ops.moe_gating(logits, k)                   # (T,k),(T,k)

    # Switch-style load-balance statistics for this column's expert slice:
    # (f_e, P_e) vectors; moe_block averages them over the data shards
    # BEFORE multiplying so distributed == single-device exactly.
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    frac_disp = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0) / Tk
    mean_prob = jnp.mean(probs, axis=0)
    f_slice = jax.lax.dynamic_slice_in_dim(frac_disp, e_lo, E_loc)
    p_slice = jax.lax.dynamic_slice_in_dim(mean_prob, e_lo, E_loc)
    aux_stats = (f_slice, p_slice)

    flat_ids = ids.reshape(Tk) - e_lo                          # local coords
    in_range = (flat_ids >= 0) & (flat_ids < E_loc)
    lid = jnp.where(in_range, flat_ids, E_loc)
    # position of each assignment within its expert, pre-sort
    oh = jax.nn.one_hot(lid, E_loc + 1, dtype=jnp.int32)       # (Tk, E+1)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0), lid[:, None],
                              axis=1)[:, 0] - 1                # (Tk,)
    key = jnp.where(in_range & (pos < cap), lid, E_loc)
    order = jnp.argsort(key)[:n_sel]          # static slice: selected only
    sel_ids = key[order]                                       # (n_sel,)
    sel_pos = pos[order]
    sel_tok = order // k
    keep = sel_ids < E_loc

    x_sel = jnp.take(xf, sel_tok, axis=0)                      # (n_sel, D)
    buf = jnp.zeros((E_loc, cap, D), xf.dtype)
    buf = buf.at[jnp.minimum(sel_ids, E_loc - 1),
                 jnp.where(keep, sel_pos, cap)].set(x_sel, mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])           # (E_loc,cap,D)

    y_sel = out[jnp.minimum(sel_ids, E_loc - 1),
                jnp.minimum(sel_pos, cap - 1)]                 # (n_sel, D)
    w_sel = jnp.take(weights.reshape(Tk).astype(xf.dtype), order)
    y_sel = jnp.where(keep[:, None], y_sel * w_sel[:, None], 0.0)
    y = jnp.zeros((T, D), xf.dtype).at[sel_tok].add(y_sel, mode="drop")
    return y, aux_stats


def moe_block(cfg: ArchConfig, p: Params, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> ((B, S, D), load-balance aux scalar)."""
    from jax.sharding import PartitionSpec as P
    from .sharding import _mesh_axes, current_rules, logical_to_pspec
    B, S, D = x.shape
    E = cfg.n_experts
    mesh_axes = _mesh_axes()
    rules = current_rules()
    tp_axis = rules.get("tp") if rules.get("expert") or rules.get("tp_ff") \
        else None
    tp_size = mesh_axes.get(tp_axis, 1) if tp_axis else 1

    if tp_size <= 1:
        # no mesh / single shard: the local path is the whole computation
        y, (f, pr) = _local_moe(cfg, x.reshape(B * S, D), p, 0, E)
        return y.reshape(B, S, D), E * jnp.sum(f * pr)

    ep = E % tp_size == 0 and rules.get("expert")
    E_loc = E // tp_size if ep else E

    mesh = jax.sharding.get_abstract_mesh()
    batch_axes = logical_to_pspec(("batch",), (B,))[0]
    xspec = P(batch_axes, None, None)
    if ep:
        wspec = {"router": P(), "w_gate": P(tp_axis, None, None),
                 "w_up": P(tp_axis, None, None),
                 "w_down": P(tp_axis, None, None)}
    else:   # expert-TP: shard d_ff
        wspec = {"router": P(), "w_gate": P(None, None, tp_axis),
                 "w_up": P(None, None, tp_axis),
                 "w_down": P(None, tp_axis, None)}

    def local_fn(x_loc, p_loc):
        Bl, Sl, Dl = x_loc.shape
        e_lo = jax.lax.axis_index(tp_axis) * E_loc if ep else 0
        y, (f, pr) = _local_moe(cfg, x_loc.reshape(Bl * Sl, Dl), p_loc,
                                e_lo, E_loc)
        y = jax.lax.psum(y, tp_axis)
        # average the statistics over the data shards FIRST (so the aux is
        # exactly the global Switch loss), then combine expert slices
        if batch_axes:
            axes_t = (batch_axes,) if isinstance(batch_axes, str) \
                else tuple(batch_axes)
            f = jax.lax.pmean(f, axes_t)
            pr = jax.lax.pmean(pr, axes_t)
        aux = E * jnp.sum(f * pr)
        if ep:
            aux = jax.lax.psum(aux, tp_axis)       # sum of expert slices
        return y.reshape(Bl, Sl, Dl), aux

    y, aux = jax.shard_map(local_fn, mesh=mesh, in_specs=(xspec, wspec),
                           out_specs=(xspec, P()), axis_names=set(mesh_axes),
                           check_vma=False)(x, p)
    return y, aux


def _block_fwd(cfg: ArchConfig, x: jax.Array, blk: Params
               ) -> Tuple[jax.Array, jax.Array]:
    h = L.rms_norm(blk["norm1"], x, cfg.norm_eps)
    x = x + L.attention_block(blk["attn"], h, n_heads=cfg.n_heads,
                              n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                              theta=cfg.rope_theta, eps=cfg.norm_eps)
    h = L.rms_norm(blk["norm2"], x, cfg.norm_eps)
    y, aux = moe_block(cfg, blk["moe"], h)
    x = x + y
    return shard(x, "batch", None, None), aux


def hidden(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
           remat: str = "none") -> Tuple[jax.Array, jax.Array]:
    """Returns (final hidden states, mean per-layer load-balance aux)."""
    x = L.embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", None, None)

    def body(carry, blk):
        h, aux_sum = carry
        h, aux = _block_fwd(cfg, h, blk)
        return (h, aux_sum + aux), None

    body = T._remat_wrap(body, remat)
    (x, aux_sum), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    return x, aux_sum / cfg.n_layers


def apply(cfg: ArchConfig, params: Params, tokens: jax.Array, *,
          remat: str = "none") -> jax.Array:
    x, _ = hidden(cfg, params, tokens, remat=remat)
    return T.logits_of(cfg, params, x)


# Switch-Transformer coefficient
AUX_LOSS_COEF = 0.01


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array], *,
            remat: str = "none") -> jax.Array:
    x, aux = hidden(cfg, params, batch["tokens"], remat=remat)
    return T.lm_loss(cfg, params, x, batch["labels"]) + AUX_LOSS_COEF * aux


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

init_cache = T.init_cache


def prefill(cfg: ArchConfig, params: Params, tokens: jax.Array,
            max_seq: Optional[int] = None) -> Tuple[jax.Array, Params]:
    from ..kernels import ops
    B, S = tokens.shape
    max_seq = max_seq or S
    x = L.embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", None, None)

    def body(h, blk):
        hn = L.rms_norm(blk["norm1"], h, cfg.norm_eps)
        q, kk, vv = L._project_qkv(blk["attn"], hn, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.hd, cfg.rope_theta,
                                   cfg.norm_eps)
        o = ops.attention(q, kk, vv, causal=True)
        h = h + o.reshape(B, S, cfg.n_heads * cfg.hd) @ blk["attn"]["wo"]
        hn = L.rms_norm(blk["norm2"], h, cfg.norm_eps)
        h = h + moe_block(cfg, blk["moe"], hn)[0]
        return shard(h, "batch", None, None), (kk, vv)

    x, (ks, vs) = lax.scan(body, x, params["blocks"])
    pad = max_seq - S
    if pad > 0:
        zeros = jnp.zeros((cfg.n_layers, B, pad, cfg.n_kv_heads, cfg.hd),
                          ks.dtype)
        ks = jnp.concatenate([ks, zeros], axis=2)
        vs = jnp.concatenate([vs, zeros], axis=2)
    cache = {"k": ks, "v": vs, "index": jnp.asarray(S, jnp.int32)}
    return T.logits_of(cfg, params, x[:, -1:]), cache


def decode_step(cfg: ArchConfig, params: Params, cache: Params,
                tokens: jax.Array) -> Tuple[jax.Array, Params]:
    B = tokens.shape[0]
    index = cache["index"]
    x = L.embed_lookup(params["embed"], tokens)

    from .sharding import current_rules
    zero_decode = bool(current_rules().get("fsdp"))

    def body(h, xs):
        blk, ck, cv = xs
        # see transformer.decode_step: ZeRO-sharded decode activations
        if zero_decode:
            h = shard(h, None, None, "fsdp")
        hn = L.rms_norm(blk["norm1"], h, cfg.norm_eps)
        o, ck, cv = L.attention_decode(blk["attn"], hn, ck, cv, index,
                                       n_heads=cfg.n_heads,
                                       n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                                       theta=cfg.rope_theta, eps=cfg.norm_eps)
        h = h + o
        hn = L.rms_norm(blk["norm2"], h, cfg.norm_eps)
        h = h + moe_block(cfg, blk["moe"], hn)[0]
        return h, (ck, cv)

    x, (ks, vs) = lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
    logits = T.logits_of(cfg, params, x)
    return logits, {"k": ks, "v": vs, "index": index + 1}
