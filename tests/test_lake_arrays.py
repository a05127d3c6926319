"""Array objects in the data lake: raw leaf buffers in their own dtypes
under a JSON manifest, stored without a copy, read back bit-exact; and
the checkpoint that writes its state through them."""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.ckpt.checkpoint import (ckpt_prefix, restore_checkpoint,
                                   save_checkpoint)
from repro.configs.base import smoke_of
from repro.core.names import Name
from repro.datalake import DataLake, DirStore, MemoryStore
from repro.optim import AdamW, constant
from repro.train.step import make_train_state

SEG = 1024           # small segments, so most leaves span several

LEAVES = {
    "bf16": lambda r: r.standard_normal((3, 700)).astype(jnp.bfloat16),
    "f32": lambda r: r.standard_normal((5, 300)).astype(np.float32),
    "int32": lambda r: r.integers(-2 ** 31, 2 ** 31 - 1, 1000,
                                  dtype=np.int32),
    "step": lambda r: np.asarray(7, np.int32),
    "sub_segment": lambda r: r.standard_normal(7).astype(np.float32),
}
STORES = ["memory", "dir"]
NAME = Name.parse("/lidc/data/arrays/obj")


def open_lakes(kind, tmp_path):
    """A lake to write and one to read: the same lake for a MemoryStore, a
    fresh DataLake on the same root for a DirStore."""
    if kind == "memory":
        lake = DataLake(store=MemoryStore(), segment_size=SEG)
        return lake, lambda: lake
    root = str(tmp_path / "lake")
    return (DataLake(store=DirStore(root), segment_size=SEG),
            lambda: DataLake(store=DirStore(root), segment_size=SEG))


def bits(a):
    a = np.asarray(a)
    return a.reshape(-1).view(np.uint8)


def first_segment_key(lake, i, nbytes):
    leaf = NAME.append(f"leaf={i}")
    return str(leaf.append("seg=0") if nbytes > lake.segment_size else leaf)


@pytest.mark.parametrize("leaf", sorted(LEAVES))
@pytest.mark.parametrize("kind", STORES)
def test_arrays_round_trip_exact_without_copy(kind, leaf, tmp_path):
    rng = np.random.default_rng(0)
    arrays = {"a/" + leaf: LEAVES[leaf](rng), "b": LEAVES[leaf](rng)}
    lake, reopen = open_lakes(kind, tmp_path)
    copies = getattr(lake.store, "copies", None)
    lake.put_arrays(NAME, arrays)
    got = reopen().get_arrays(NAME)
    assert list(got) == list(arrays)
    for k, a in arrays.items():
        assert got[k].dtype == a.dtype and got[k].shape == a.shape, k
        np.testing.assert_array_equal(bits(got[k]), bits(a))
    if kind == "memory":
        assert lake.store.copies == copies
        for i, a in enumerate(arrays.values()):
            seg = lake.store.get(first_segment_key(lake, i, a.nbytes))
            assert np.shares_memory(np.frombuffer(seg, np.uint8), a)


def small_state(rng):
    return {"params": {"w": jnp.asarray(LEAVES["bf16"](rng))},
            "opt": {"m": jnp.asarray(LEAVES["f32"](rng))},
            "step": jnp.asarray(LEAVES["step"](rng))}


@pytest.mark.parametrize("missing", ["manifest", "leaf_segment"])
@pytest.mark.parametrize("kind", STORES)
def test_torn_arrays_read_as_missing(kind, missing, tmp_path):
    lake, reopen = open_lakes(kind, tmp_path)
    state = small_state(np.random.default_rng(1))
    name = save_checkpoint(lake, "torn", 3, state)
    reader = reopen()
    leaf0 = name.append("leaf=0")          # params/w: several segments
    key = str(name if missing == "manifest" else leaf0.append("seg=1"))
    assert reader.store.get(key) is not None
    reader.store.delete(key)
    assert reader.get_arrays(name) is None
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(reader, "torn", jax.eval_shape(lambda: state))


@pytest.mark.parametrize("kind", STORES)
def test_npz_blob_of_older_lakes_still_read(kind, tmp_path):
    rng = np.random.default_rng(2)
    arrays = {"tokens": LEAVES["int32"](rng), "x": LEAVES["f32"](rng)}
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    lake, reopen = open_lakes(kind, tmp_path)
    lake.put_bytes(NAME, buf.getvalue(), meta={"kind": "arrays", "n": 2})
    got = reopen().get_arrays(NAME)
    assert sorted(got) == sorted(arrays)
    for k, a in arrays.items():
        assert got[k].dtype == a.dtype
        np.testing.assert_array_equal(got[k], a)


class RecordingStore(MemoryStore):
    """A MemoryStore that remembers the order of its puts."""

    def __init__(self):
        super().__init__()
        self.order = []

    def put(self, key, blob):
        self.order.append(key)
        super().put(key, blob)


def test_save_checkpoint_stores_device_bytes_without_copy():
    cfg = smoke_of("qwen3-1.7b")
    state = make_train_state(cfg, jax.random.PRNGKey(0),
                             AdamW(lr=constant(1e-3)))
    device_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    assert any(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(state))
    lake = DataLake(store=RecordingStore())
    tracing.drain()
    tracing.enable()
    try:
        name = save_checkpoint(lake, "runB", 5, state)
    finally:
        tracing.disable()
        spans = tracing.drain()
    save, = [s for s in spans if s["name"] == "ckpt.save"]
    put, = [s for s in spans if s["name"] == "lake.put"]
    assert save["bytes"] == device_bytes          # no leaf upcast
    assert put["store_copies"] == 0

    order = lake.store.order
    manifest = order.index(str(name))
    latest = order.index(str(ckpt_prefix("runB").append("latest")))
    leaves = [i for i, k in enumerate(order)
              if k.startswith(str(name) + "/leaf=")]
    assert len(leaves) >= len(jax.tree.leaves(state))
    assert max(leaves) < manifest < latest

    got = lake.get_arrays(name)
    for a, b in zip(jax.tree.leaves(jax.device_get(state)), got.values()):
        assert b.dtype == a.dtype                 # bf16 stays bf16
        np.testing.assert_array_equal(bits(b), bits(a))
