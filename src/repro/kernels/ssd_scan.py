"""Mamba2 SSD inter-chunk state recurrence (Pallas TPU).

The chunked SSD algorithm reduces each chunk to an (P x N) state update
``S_c+1 = a_c * S_c + X_c``; this sequential pass over chunks is the only
part of SSD that cannot be a big matmul.  The kernel walks the chunk axis
with the running state resident in VMEM, emitting the *prefix* state (the
state entering each chunk) and the final state — one HBM read and one HBM
write per chunk state, zero re-materialization.

Decay factors arrive via scalar prefetch (SMEM).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_state_scan"]


def _scan_kernel(decay_ref, x_ref, init_ref, prefix_ref, final_ref, s_ref):
    b = pl.program_id(0)
    h = pl.program_id(1)
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = init_ref[...].astype(jnp.float32)

    s = s_ref[...]
    prefix_ref[...] = s.astype(prefix_ref.dtype)
    a = decay_ref[b, c, h]
    s_ref[...] = a * s + x_ref[...].astype(jnp.float32)

    @pl.when(c == nc - 1)
    def _fin():
        final_ref[...] = s_ref[...].astype(final_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_state_scan(chunk_states: jax.Array, chunk_decays: jax.Array,
                   init_state: Optional[jax.Array] = None, *,
                   interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """chunk_states: (B,C,H,P,N); chunk_decays: (B,C,H).
    Returns (prefix (B,C,H,P,N), final (B,H,P,N))."""
    B, C, H, P, N = chunk_states.shape
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), chunk_states.dtype)
    decays = chunk_decays.astype(jnp.float32)

    grid = (B, H, C)
    prefix, final = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, None, P, N),
                             lambda b, h, c, *_: (b, c, h, 0, 0)),
                pl.BlockSpec((None, None, P, N),
                             lambda b, h, c, *_: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, None, P, N),
                             lambda b, h, c, *_: (b, c, h, 0, 0)),
                pl.BlockSpec((None, None, P, N),
                             lambda b, h, c, *_: (b, h, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, C, H, P, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(decays, chunk_states, init_state)
    return prefix, final
