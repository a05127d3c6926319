"""Plain float32 reference of the dense decoders' training: the forward
of ``dense.py`` with its norm weights and biases as parameters, mean
token cross-entropy over the tied vocabulary projection, and AdamW.

The data is the deterministic synthetic stream the program trains on, a
copy of its generator (``data/pipeline.py``'s ``SyntheticLM``: an
alphabet of 64 tokens, each the last one times 3 plus 7 with
probability 0.9, else uniform), so that the reference sees the same
batches without importing the program.  AdamW is as the configuration
file states it: b1 0.9, b2 0.95, eps 1e-8, global-norm clip 1.0,
weight decay 0.1 on every leaf of two or more dimensions of the layer-
stacked parameters, warmup-cosine learning rate.

``precision="fp8"`` is the control: matrix products in float8, as in
``dense.py``, and the parameters kept in float8 (with a scale per row)
as the program keeps them in bfloat16.  ``rows`` keeps only the first
rows of each batch, which plants the fault of a step that leaves half
the batch out.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense

F32 = jnp.float32
# reference weight name -> the leaf path it is compared with
LEAVES = {"wq": "blocks/attn/wq", "wk": "blocks/attn/wk",
          "wv": "blocks/attn/wv", "wo": "blocks/attn/wo",
          "bq": "blocks/attn/bq", "bk": "blocks/attn/bk",
          "bv": "blocks/attn/bv", "q_norm": "blocks/attn/q_norm",
          "k_norm": "blocks/attn/k_norm", "w_gate": "blocks/mlp/w_gate",
          "w_up": "blocks/mlp/w_up", "w_down": "blocks/mlp/w_down",
          "norm1": "blocks/norm1/w", "norm2": "blocks/norm2/w"}


def synthetic_batches(vocab: int, batch: int, seq: int, seed: int, n: int
                      ) -> List[Dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    v = int(min(64, vocab))
    out = []
    for _ in range(n):
        toks = np.empty((batch, seq + 1), np.int64)
        toks[:, 0] = rng.integers(0, v, batch)
        noise = rng.random((batch, seq))
        rand = rng.integers(0, v, (batch, seq))
        for t in range(1, seq + 1):
            det = (toks[:, t - 1] * 3 + 7) % v
            toks[:, t] = np.where(noise[:, t - 1] < 0.9, det, rand[:, t - 1])
        out.append({"tokens": toks[:, :-1].astype(np.int32),
                    "labels": toks[:, 1:].astype(np.int32)})
    return out


def init(m: Dict, weight_seed: int) -> Dict[str, jax.Array]:
    """The layer-stacked parameters, float32 values of the bfloat16 ones."""
    ke, layer_keys = dense.keys(weight_seed, m)
    layers = jax.vmap(lambda k: dense.layer(m, k))(layer_keys)
    params = {LEAVES[k]: v for k, v in layers.items()}
    params["embed/table"] = dense.embedding(m, ke)
    params["final_norm/w"] = jnp.ones((m["d"],), F32)
    return params


def loss_fn(m: Dict, params, tokens, labels, precision: str):
    x = params["embed/table"][tokens]
    blocks = {k: params[v] for k, v in LEAVES.items() if v in params}

    @jax.checkpoint
    def body(x, w):
        return dense.block(m, x, w, precision), None

    x, _ = jax.lax.scan(body, x, blocks)
    x = dense.rms_norm(x, params["final_norm/w"], m["eps"])
    logits = dense.matmul(x, params["embed/table"].T, precision)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def lr_at(step, peak: float, warmup: int, total: int, floor: float = 0.1):
    s = step.astype(F32)
    warm = peak * s / max(warmup, 1)
    frac = jnp.clip((s - warmup) / max(total - warmup, 1), 0.0, 1.0)
    cos = peak * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(s < warmup, warm, cos)


@partial(jax.jit, static_argnames=("m_items", "precision", "total"),
         donate_argnums=(0,))
def _step(state, tokens, labels, *, m_items, precision, total):
    m = dict(m_items)
    params, mom, vel, step = state
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn, argnums=1)(
            m, params, tokens, labels, precision)
    step = step + 1
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, 1.0 / (gnorm + 1e-9))
    c1, c2 = 1 - 0.9 ** step.astype(F32), 1 - 0.95 ** step.astype(F32)
    lr = lr_at(step, 3e-3, max(total // 20, 2), total)
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k] * scale
        new_m[k] = 0.9 * mom[k] + 0.1 * g
        new_v[k] = 0.95 * vel[k] + 0.05 * g * g
        delta = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + 1e-8)
        if p.ndim >= 2:
            delta = delta + 0.1 * p
        new_p[k] = p - lr * delta
        if precision == "fp8":      # the control keeps its weights in fp8
            new_p[k] = dense.fp8(new_p[k], -1)
    grad_norms = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in grads.items()}
    return (new_p, new_m, new_v, step), loss, grad_norms


def train(cfg: Dict, weight_seed: int, data_seed: int, batch: int, seq: int,
          steps: int, total: int, *, precision: str = "f32",
          rows: Optional[int] = None) -> Dict[str, object]:
    """``steps`` reference steps of a run of ``total`` steps: the losses,
    the first step's gradient norm per leaf, and per leaf the norm of the
    parameters' change and of Adam's first moment after the last step;
    ``p0``, the initial parameters, stay on the device."""
    m = dense.dims(cfg)
    p0 = init(m, weight_seed)

    def zeros():
        return {k: jnp.zeros_like(v) for k, v in p0.items()}

    state = ({k: v.copy() for k, v in p0.items()}, zeros(), zeros(),
             jnp.zeros((), jnp.int32))
    losses, first_grad = [], None
    for b in synthetic_batches(m["V"], batch, seq, data_seed, steps):
        toks, labs = b["tokens"], b["labels"]
        if rows is not None:
            toks, labs = toks[:rows], labs[:rows]
        state, loss, gn = _step(state, toks, labs,
                                m_items=tuple(sorted(m.items())),
                                precision=precision, total=total)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {k: float(v) for k, v in gn.items()}
    params, mom = state[0], state[1]
    return {"losses": losses, "first_grad_norm": first_grad,
            "update_norm": {k: float(jnp.linalg.norm(params[k] - p0[k]))
                            for k in params},
            "moment_norm": {k: float(jnp.linalg.norm(mom[k])) for k in mom},
            "p0": p0}


def leaf_gap(got: Dict[str, float], want: Dict[str, float],
             keep: List[str]) -> Dict[str, float]:
    """Per leaf, |‖got‖ - ‖want‖| over the larger of ‖want‖ and the median
    leaf's ‖want‖."""
    med = float(np.median([want[k] for k in keep]))
    return {k: abs(got[k] - want[k]) / max(want[k], med) for k in keep}


def moving_leaves(first_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's."""
    med = float(np.median(list(first_grad.values())))
    return sorted(k for k, g in first_grad.items() if g >= 1e-3 * med)


def norms_from_checkpoint(arrays: Dict[str, np.ndarray],
                          p0: Dict[str, jax.Array]) -> Dict[str, Dict]:
    """The program's parameter change and first moment per leaf, from its
    checkpoint's flat arrays (``params/<leaf>``, ``opt/.m/<leaf>``)."""
    upd, mom = {}, {}
    for k, v in p0.items():
        upd[k] = float(jnp.linalg.norm(jnp.asarray(arrays["params/" + k], F32)
                                       - v))
        mom[k] = float(jnp.linalg.norm(jnp.asarray(arrays["opt/.m/" + k],
                                                   F32)))
    return {"update_norm": upd, "moment_norm": mom}
