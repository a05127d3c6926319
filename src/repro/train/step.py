"""Train-step factory: loss -> grads -> (optionally compressed) reduce ->
AdamW, with remat and microbatch gradient accumulation.

The returned step is a plain function to be ``jax.jit``-ed by the caller
with explicit in/out shardings (see launch/dryrun.py and launch/train.py);
nothing here touches devices.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..models.model import bundle_for
from ..optim.adamw import AdamW, AdamWState

Params = Any
State = Dict[str, Any]


def make_train_state(cfg: ArchConfig, key, optimizer: AdamW) -> State:
    bundle = bundle_for(cfg)
    params = bundle.init(cfg, key)
    return {"params": params, "opt": optimizer.init(params)}


def train_state_shape(cfg: ArchConfig, optimizer: AdamW):
    """eval_shape of the train state (dry-run input spec)."""
    return jax.eval_shape(
        lambda k: make_train_state(cfg, k, optimizer),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def _split_microbatches(batch: Dict[str, jax.Array], n: int):
    def split(x):
        B = x.shape[0]
        assert B % n == 0, (B, n)
        return x.reshape(n, B // n, *x.shape[1:])
    return jax.tree.map(split, batch)


def make_train_step(cfg: ArchConfig, optimizer: AdamW, *,
                    remat: str = "none", microbatch: int = 1,
                    compress_pods: bool = False,
                    mesh=None) -> Callable[[State, Dict], Tuple[State, Dict]]:
    bundle = bundle_for(cfg)

    def loss_of(params, batch):
        return bundle.loss_fn(cfg, params, batch, remat=remat)

    def grads_of(params, batch):
        if microbatch <= 1:
            return jax.value_and_grad(loss_of)(params, batch)
        mbs = _split_microbatches(batch, microbatch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def acc(carry, mb):
            tot_loss, tot_g = carry
            l, g = jax.value_and_grad(loss_of)(params, mb)
            tot_g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 tot_g, g)
            return (tot_loss + l, tot_g), None

        (loss, grads), _ = jax.lax.scan(acc, (jnp.zeros(()), zero), mbs)
        inv = 1.0 / microbatch
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    if compress_pods:
        assert mesh is not None and "pod" in mesh.axis_names
        from jax.sharding import PartitionSpec as P
        from ..optim.compress import compressed_psum_pod

        def grads_compressed(params, batch):
            # manual over 'pod' only; 'data'/'model' stay automatic so the
            # partitioner still handles TP/DP inside each pod.
            def per_pod(params, batch):
                loss, grads = grads_of(params, batch)
                grads = jax.tree.map(
                    lambda g: compressed_psum_pod(g, "pod"), grads)
                loss = jax.lax.pmean(loss, "pod")
                return loss, grads

            pspec = jax.tree.map(lambda _: P(), params)
            bspec = jax.tree.map(lambda _: P("pod"), batch)
            return jax.shard_map(
                per_pod, mesh=mesh, in_specs=(pspec, bspec),
                out_specs=(P(), pspec),
                axis_names={"pod"}, check_vma=False)(params, batch)

        grad_fn = grads_compressed
    else:
        grad_fn = grads_of

    def train_step(state: State, batch: Dict[str, jax.Array]
                   ) -> Tuple[State, Dict[str, jax.Array]]:
        loss, grads = grad_fn(state["params"], batch)
        params, opt, metrics = optimizer.update(grads, state["opt"],
                                                state["params"])
        new_state = {"params": params, "opt": opt}
        return new_state, {"loss": loss, **metrics}

    return train_step


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def make_prefill(cfg: ArchConfig):
    bundle = bundle_for(cfg)

    def prefill(params, inputs, max_seq=None):
        if cfg.family == "encdec":
            return bundle.prefill(cfg, params, inputs, max_seq=max_seq)
        return bundle.prefill(cfg, params, inputs["tokens"], max_seq=max_seq)

    return prefill


def make_serve_step(cfg: ArchConfig):
    bundle = bundle_for(cfg)

    def serve_step(params, cache, tokens):
        return bundle.decode_step(cfg, params, cache, tokens)

    return serve_step
