"""Pipeline parallelism (GPipe schedule) for the dense transformer.

Layer stages are sharded over a 'pipe' mesh axis; microbatches flow
through the stages via ``lax.ppermute`` inside a shard_map, with the
classic (n_micro + n_stages - 1)-tick schedule.  Autodiff through the
shard_map/ppermute gives the backward pipeline for free (activations are
held per tick — GPipe-style memory, pair with microbatching).

This is an *optional* distribution mode (the production dry-run meshes use
DP×TP; PP composes on fleets with a spare axis).  Mathematical equivalence
with the plain loss is asserted in tests/test_pipeline.py — same loss and
same gradients as the sequential model.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..models import layers as L
from ..models import transformer as T

Params = Dict[str, Any]

__all__ = ["make_pp_loss_fn", "make_pp_mesh"]


def make_pp_mesh(n_stages: int, extra_axes: Tuple[Tuple[str, int], ...] = ()):
    from ..launch.mesh import make_mesh
    shape = (n_stages,) + tuple(n for _, n in extra_axes)
    names = ("pipe",) + tuple(a for a, _ in extra_axes)
    return make_mesh(shape, names)


def make_pp_loss_fn(cfg: ArchConfig, mesh, *, n_stages: int, n_micro: int):
    """Returns loss_fn(params, batch) running the GPipe schedule."""
    assert cfg.n_layers % n_stages == 0, (cfg.n_layers, n_stages)
    l_per = cfg.n_layers // n_stages

    def stage_fn(blocks, embed_tbl, final_w, out_w, tokens, labels):
        # manual over 'pipe': blocks is this stage's (l_per, ...) slice
        sid = lax.axis_index("pipe")
        S = n_stages
        B, S_len = tokens.shape
        assert B % n_micro == 0
        Bm = B // n_micro
        toks_mb = tokens.reshape(n_micro, Bm, S_len)
        lbls_mb = labels.reshape(n_micro, Bm, S_len)
        dt = embed_tbl.dtype
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            h_out_prev, loss_sum = carry
            # hand the previous tick's output downstream
            h_recv = lax.ppermute(h_out_prev, "pipe", fwd_perm)
            mb = t - sid
            active = jnp.logical_and(mb >= 0, mb < n_micro)
            mb_c = jnp.clip(mb, 0, n_micro - 1)
            x0 = jnp.take(embed_tbl, toks_mb[mb_c], axis=0)
            x = jnp.where(sid == 0, x0, h_recv.astype(dt))

            def body(h, blk):
                return T._block_fwd(cfg, h, blk), None

            y, _ = lax.scan(body, x, blocks)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage: loss for this microbatch
            xn = L.rms_norm({"w": final_w}, y, cfg.norm_eps)
            logits = xn @ out_w
            mb_loss = L.cross_entropy_loss(logits, lbls_mb[mb_c])
            take = jnp.logical_and(active, sid == S - 1)
            loss_sum = loss_sum + jnp.where(take, mb_loss, 0.0)
            return (y, loss_sum), None

        h0 = jnp.zeros((Bm, S_len, cfg.d_model), dt)
        (_, loss_sum), _ = lax.scan(
            tick, (h0, jnp.zeros((), jnp.float32)),
            jnp.arange(n_micro + S - 1))
        # per-stage partial (nonzero only on the last stage); summed outside
        # the shard_map — a rank-1 sharded output instead of a replicated
        # scalar psum, which old shard_map cannot transpose through
        return loss_sum.reshape(1)

    smapped = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P("pipe"), P(), P(), P(), P(), P()),
        out_specs=P("pipe"), axis_names={"pipe"}, check_vma=False)

    def loss_fn(params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        blocks = jax.tree.map(
            lambda t: t.reshape((n_stages, l_per) + t.shape[1:]),
            params["blocks"])
        out_w = T.out_proj(cfg, params)
        return jnp.sum(smapped(blocks, params["embed"]["table"],
                               params["final_norm"]["w"], out_w,
                               batch["tokens"], batch["labels"])) / n_micro

    return loss_fn
