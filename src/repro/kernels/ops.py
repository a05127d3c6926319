"""jit'd dispatch wrappers: one call site, three implementations.

The implementation follows the platform: the Pallas kernels on a TPU, the
pure-jnp reference everywhere else (Pallas TPU kernels cannot lower on the
host backend).  Callers may pin one explicitly with :func:`use_impl`:

* ``ref``      — pure-jnp oracle (also the reference a chip run compares to)
* ``pallas``   — real Pallas kernels (TPU target)
* ``interpret``— Pallas kernels in interpret mode (CPU correctness tests)

The choice is read when a model function is *traced*: a function jitted
under one implementation keeps it, so a comparison builds fresh jitted
closures per implementation.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Iterator, Optional, Tuple

import jax

from . import ref

__all__ = ["use_impl", "get_impl", "attention", "decode_attention",
           "ssd_state_scan", "moe_gating"]

_IMPLS = ("ref", "pallas", "interpret")
_IMPL: Optional[str] = None     # None: derive from the platform


def get_impl() -> str:
    if _IMPL is not None:
        return _IMPL
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@contextmanager
def use_impl(impl: str) -> Iterator[None]:
    """Pin the kernel implementation for code traced inside the block."""
    global _IMPL
    assert impl in _IMPLS, impl
    prev, _IMPL = _IMPL, impl
    try:
        yield
    finally:
        _IMPL = prev


_CHUNK_THRESHOLD = 1024   # chunk the XLA fallback above this query length
_CHUNK_Q = 512


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True) -> jax.Array:
    impl = get_impl()
    if impl == "ref":
        if q.shape[1] > _CHUNK_THRESHOLD:
            return ref.attention_chunked(q, k, v, causal=causal,
                                         chunk_q=_CHUNK_Q)
        return ref.attention_ref(q, k, v, causal=causal)
    from .flash_attention import flash_attention
    return flash_attention(q, k, v, causal=causal,
                           interpret=(impl == "interpret"))


def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     length: jax.Array, *, seq_shard: bool = False
                     ) -> jax.Array:
    # seq_shard is handled transparently by the SPMD partitioner: with the
    # cache sequence dim sharded over 'data', the softmax reductions become
    # all-reduces. The flag is kept for the explicit shard_map path.
    impl = get_impl()
    if impl == "ref":
        return ref.decode_attention_ref(q, cache_k, cache_v, length)
    from .decode_attention import flash_decode
    return flash_decode(q, cache_k, cache_v, length,
                        interpret=(impl == "interpret"))


def ssd_state_scan(chunk_states: jax.Array, chunk_decays: jax.Array,
                   init_state: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    impl = get_impl()
    if impl == "ref":
        return ref.ssd_state_scan_ref(chunk_states, chunk_decays, init_state)
    from .ssd_scan import ssd_state_scan as kernel
    return kernel(chunk_states, chunk_decays, init_state,
                  interpret=(impl == "interpret"))


def moe_gating(logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    impl = get_impl()
    if impl == "ref":
        return ref.moe_gating_ref(logits, k)
    from .moe_gating import moe_gating as kernel
    return kernel(logits, k, interpret=(impl == "interpret"))
