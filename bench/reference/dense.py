"""Plain float32 reference of the dense decoders (Qwen2, Qwen3), written
from the published equations and independent of the program's models.

Per layer, with x the residual stream (RMSNorm weights and biases are
part of the weights, the norms' being 1 at initialisation):

    h = RMSNorm(x);  q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
    q, k = RMSNorm_head(q), RMSNorm_head(k)                (Qwen3's qk-norm)
    q, k = RoPE(q), RoPE(k)          (theta from the config, rotate-half form)
    x = x + softmax(q k^T / sqrt(hd) + causal mask) v Wo  (grouped KV heads)
    x = x + (silu(RMSNorm(x) Wgate) * RMSNorm(x) Wup) Wdown
    logits = RMSNorm(x) E^T                                 (tied embedding)

The weights are drawn here from the program's fixed key (0, the one
``ServeExecutor.engine`` uses) with the same random draws the program
makes (``jax.random`` key splits, N(0, 1/fan_in) matrices, N(0, 0.02^2)
embedding, rounded to the served bfloat16): the reference takes nothing
the program made.  They are drawn layer by layer inside the scan, so no
more than one layer's float32 weights exist.

``precision="fp8"`` is the control: every matrix product's weights and
inputs rounded to float8 e4m3 with a per-channel scale, the step below
the bfloat16 the configuration states.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: Dict) -> Dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "L": int(cfg["num_hidden_layers"]), "H": h,
            "K": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "ff": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "bias": bool(cfg["attention_bias"]),
            "qk_norm": bool(cfg["qk_norm"])}


# -- weights ----------------------------------------------------------------

def _matrix(key, shape, fan_in: int, dtype) -> jax.Array:
    w = jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype).astype(F32)


def keys(weight_seed: int, m: Dict):
    """(embedding key, one key per layer)."""
    ke, kb, _ = jax.random.split(jax.random.PRNGKey(weight_seed), 3)
    return ke, jax.random.split(kb, m["L"])


def embedding(m: Dict, ke, dtype=jnp.bfloat16) -> jax.Array:
    return (jax.random.normal(ke, (m["V"], m["d"])) * 0.02
            ).astype(dtype).astype(F32)


def layer(m: Dict, key, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """One layer's weights, float32, from its key."""
    d, H, K, hd, ff = m["d"], m["H"], m["K"], m["hd"], m["ff"]
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    w = {"wq": _matrix(ka[0], (d, H * hd), d, dtype),
         "wk": _matrix(ka[1], (d, K * hd), d, dtype),
         "wv": _matrix(ka[2], (d, K * hd), d, dtype),
         "wo": _matrix(ka[3], (H * hd, d), H * hd, dtype),
         "w_gate": _matrix(km[0], (d, ff), d, dtype),
         "w_up": _matrix(km[1], (d, ff), d, dtype),
         "w_down": _matrix(km[2], (ff, d), ff, dtype),
         "norm1": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32)}
    if m["bias"]:
        w.update(bq=jnp.zeros((H * hd,), F32), bk=jnp.zeros((K * hd,), F32),
                 bv=jnp.zeros((K * hd,), F32))
    if m["qk_norm"]:
        w.update(q_norm=jnp.ones((hd,), F32), k_norm=jnp.ones((hd,), F32))
    return w


# -- equations ----------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (B, S, heads, hd); rotate-half RoPE at positions ``pos`` (S,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) * 2.0 / x.shape[-1])
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``; the
    gradient passes through unrounded (a straight-through estimator, as
    fp8 training keeps its gradients in a wider type)."""
    scale = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0)
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(x, w, precision: str):
    if precision == "fp8":
        x, w = fp8(x, -1), fp8(w, 0)
    return x @ w


def block(m: Dict, x, w, precision: str):
    B, S, _ = x.shape
    H, K, hd, eps = m["H"], m["K"], m["hd"], m["eps"]
    h = rms_norm(x, w["norm1"], eps)
    q, k, v = (matmul(h, w[n], precision) for n in ("wq", "wk", "wv"))
    if m["bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if m["qk_norm"]:
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    pos = jnp.arange(S)
    q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + matmul(o.reshape(B, S, H * hd), w["wo"], precision)
    h = rms_norm(x, w["norm2"], eps)
    act = jax.nn.silu(matmul(h, w["w_gate"], precision)) \
        * matmul(h, w["w_up"], precision)
    return x + matmul(act, w["w_down"], precision)


def hidden(m: Dict, weight_seed: int, tokens, precision: str = "f32"):
    """Final-normed hidden states (B, S, d) and the float32 embedding."""
    ke, layer_keys = keys(weight_seed, m)
    emb = embedding(m, ke)
    x = emb[tokens]

    def body(x, key):
        return block(m, x, layer(m, key), precision), None

    x, _ = jax.lax.scan(body, x, layer_keys)
    return rms_norm(x, jnp.ones((m["d"],), F32), m["eps"]), emb


# -- the served-token comparison ----------------------------------------------------

@partial(jax.jit, static_argnames=("m_items", "control"))
def _served_gaps(weight_seed, tokens, positions, targets, *, m_items,
                 control: bool):
    m = dict(m_items)
    with jax.default_matmul_precision("highest"):
        x, emb = hidden(m, weight_seed, tokens)
        xc = hidden(m, weight_seed, tokens, "fp8")[0] if control else x

        def row(args):
            xb, xcb, pb, tb = args
            logits = xb[pb] @ emb.T                       # (N, V)
            best = jnp.max(logits, -1)
            if control:
                tb = jnp.argmax(matmul(xcb[pb], emb.T, "fp8"), -1)
            chosen = jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
            return best - chosen

        return jax.lax.map(row, (x, xc, positions, targets))


GROUP = 4  # requests per reference call: one program, bounded memory


def served_gaps(cfg: Dict, weight_seed: int, prompts: Sequence[List[int]],
                outs: Sequence[List[int]], pad_to: int, n_pad: int, *,
                control: bool = False) -> List[np.ndarray]:
    """For each request, at each served position, how far the served
    token's reference logit lies below the reference's best logit.

    The reference runs once over each prompt followed by its served tokens
    (all but the last, whose successor was never computed), padded to
    ``pad_to`` tokens, ``n_pad`` served tokens and groups of ``GROUP``
    requests, so that one program serves every run.  With ``control`` the token compared is the
    one the fp8 control ranks first instead."""
    m = dims(cfg)
    m_items = tuple(sorted(m.items()))
    n_max = n_pad
    out: List[np.ndarray] = []
    for g in range(0, len(prompts), GROUP):
        tokens = np.zeros((GROUP, pad_to), np.int32)
        positions = np.zeros((GROUP, n_max), np.int32)
        targets = np.zeros((GROUP, n_max), np.int32)
        group = list(zip(prompts[g:g + GROUP], outs[g:g + GROUP]))
        for b, (p, o) in enumerate(group):
            seq = list(p) + list(o[:-1])
            if len(seq) > pad_to:
                raise ValueError(f"request of {len(seq)} tokens > {pad_to}")
            tokens[b, :len(seq)] = seq
            positions[b, :len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
            targets[b, :len(o)] = o
        gaps = np.asarray(_served_gaps(
            jnp.uint32(weight_seed), tokens, positions, targets,
            m_items=m_items, control=control))
        out += [gaps[b, :len(o)] for b, (_, o) in enumerate(group)]
    return out
