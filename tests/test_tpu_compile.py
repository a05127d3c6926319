"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and one of its devices.  Each test asserts that the kernel made
it into the program as a ``tpu_custom_call``.  The topology is described
inside a fixture (never at import), so every test worker collects the same
tests and only the one running this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gating import moe_gating
from repro.kernels.ssd_scan import ssd_state_scan
from repro.models import bundle_for
from repro.serve.engine import serve_programs


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


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "qwen2-0.5b"])
def test_flash_decode_stacked_cache(one_chip, arch):
    cfg = get_config(arch)
    B, Smax = 8, 1024
    q = _sds((B, 1, cfg.n_heads, cfg.hd), cfg.dtype, one_chip)
    cache = _sds((cfg.n_layers, B, Smax, cfg.n_kv_heads, cfg.hd), cfg.dtype,
                 one_chip)
    lengths = _sds((B,), jnp.int32, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    text = _compiled_text(flash_decode, q, cache, cache, lengths, layer)
    assert "tpu_custom_call" in text


def _engine_program(one_chip, arch, B, Smax, which):
    """The serve engine's own decode (or prefill-into-slot) program,
    compiled for the described chip with the Pallas kernels."""
    cfg = get_config(arch)
    bundle = bundle_for(cfg)
    with ops.use_impl("pallas"):
        decode, prefill, formats = serve_programs(cfg, one_chip)
        params = jax.eval_shape(lambda: bundle.init(cfg, jax.random.PRNGKey(0)))
        cache = jax.eval_shape(lambda: bundle.init_cache(cfg, B, Smax))
        cache["index"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        cache = {k: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=formats[k])
                 for k, a in cache.items()}
        params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                              params)
        if which == "decode":
            args = (params, cache, _sds((B, 1), jnp.int32, one_chip))
            text = decode.lower(*args).compile().as_text()
        else:
            args = (params, cache, _sds((1, 1280), jnp.int32, one_chip),
                    _sds((), jnp.int32, one_chip))
            text = prefill.lower(*args).compile().as_text()
    return cfg, text


def _cache_ops(text, shapes):
    """Instructions that copy, transpose, slice or update a cache-sized
    array: ``[(name, op, shape)]``."""
    found = []
    for m in re.finditer(r"%([\w.-]+) = \w+\[([\d,]*)\]\S* "
                         r"(copy|transpose|dynamic-slice|"
                         r"dynamic-update-slice)\(", text):
        if m.group(2) in shapes:
            found.append(m.groups())
    return found


@pytest.mark.parametrize("arch,B,Smax", [("qwen3-1.7b", 16, 2048),
                                         ("qwen2-0.5b", 16, 2048)])
def test_decode_step_reads_and_writes_the_cache_in_place(one_chip, arch, B,
                                                         Smax):
    """The engine's decode step: the donated cache is aliased to the
    output, the decode kernel is there by name, and no cache-sized array is
    copied, transposed, sliced out or written back — per layer or whole."""
    cfg, text = _engine_program(one_chip, arch, B, Smax, "decode")
    K, hd, L = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    shapes = {f"{B},{Smax},{K},{hd}", f"{B},{K},{Smax},{hd}",
              f"{L},{B},{Smax},{K},{hd}"}
    header = text.splitlines()[0]            # holds input_output_alias
    entry = text[text.index("\nENTRY "):]
    cache_params = re.findall(
        rf"= bf16\[{L},{B},{Smax},{K},{hd}\]\S* parameter\((\d+)\)", entry)
    assert len(cache_params) == 2
    for n in cache_params:
        assert f"({n}, {{}}, may-alias)" in header
    assert re.search(r"%flash_decode\.\d+ = .* custom-call\(", text)
    assert _cache_ops(text, shapes) == []


def test_prefill_writes_its_slot_in_place(one_chip):
    """Prefill into a slot: the donated cache is aliased and only the
    prompt's rows are written, with no copy of the whole cache."""
    cfg, text = _engine_program(one_chip, "qwen3-1.7b", 16, 2048, "prefill")
    stacked = f"{cfg.n_layers},16,2048,{cfg.n_kv_heads},{cfg.hd}"
    entry = text[text.index("\nENTRY "):]
    for n in re.findall(rf"= bf16\[{stacked}\]\S* parameter\((\d+)\)",
                        entry):
        assert f"({n}, {{}}, may-alias)" in text.splitlines()[0]
    assert not [op for op in _cache_ops(text, {stacked})
                if op[2] != "dynamic-update-slice"]


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
