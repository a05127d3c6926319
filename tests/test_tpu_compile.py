"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and one of its devices.  Each test asserts that the kernel made
it into the program as a ``tpu_custom_call``.  The topology is described
inside a fixture (never at import), so every test worker collects the same
tests and only the one running this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gating import moe_gating
from repro.kernels.ssd_scan import ssd_state_scan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without a chip: keep it out of whatever cache is configured
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_padded_prompt_qwen3(one_chip):
    """A 200-token prompt pads to two 128-token tiles."""
    cfg = get_config("qwen3-1.7b")
    S = 200
    q = _sds((1, S, cfg.n_heads, cfg.hd), cfg.dtype, one_chip)
    kv = _sds((1, S, cfg.n_kv_heads, cfg.hd), cfg.dtype, one_chip)
    text = _compiled_text(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "qwen2-0.5b"])
def test_flash_decode_per_row_lengths(one_chip, arch):
    cfg = get_config(arch)
    B, Smax = 8, 1024
    q = _sds((B, 1, cfg.n_heads, cfg.hd), cfg.dtype, one_chip)
    cache = _sds((B, Smax, cfg.n_kv_heads, cfg.hd), cfg.dtype, one_chip)
    lengths = _sds((B,), jnp.int32, one_chip)
    text = _compiled_text(flash_decode, q, cache, cache, lengths)
    assert "tpu_custom_call" in text


def test_flash_attention_vjp_qwen3(one_chip):
    cfg = get_config("qwen3-1.7b")
    S = 256
    q = _sds((2, S, cfg.n_heads, cfg.hd), cfg.dtype, one_chip)
    kv = _sds((2, S, cfg.n_kv_heads, cfg.hd), cfg.dtype, one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    # value_and_grad, as the train step: the loss keeps the forward kernel
    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          q, kv, kv)
    assert "tpu_custom_call" in text


def test_ssd_scan_zamba2(one_chip):
    cfg = get_config("zamba2-2.7b")
    H = cfg.ssm_heads
    P = cfg.ssm_expand * cfg.d_model // H
    C = 4096 // cfg.chunk
    states = _sds((1, C, H, P, cfg.ssm_state), jnp.float32, one_chip)
    decays = _sds((1, C, H), jnp.float32, one_chip)
    text = _compiled_text(lambda s, a: ssd_state_scan(s, a), states, decays)
    assert "tpu_custom_call" in text


def test_moe_gating_e128_k8(one_chip):
    logits = _sds((2048, 128), jnp.float32, one_chip)
    text = _compiled_text(lambda x: moe_gating(x, 8), logits)
    assert "tpu_custom_call" in text
