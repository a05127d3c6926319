"""ServeEngine edge cases: slots, EOS, max_new=0, KV checkpoint/restore."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_of
from repro.kernels.ops import use_impl
from repro.models import bundle_for
from repro.serve.engine import (SUPPORTED_FAMILIES, ServeEngine,
                                UnsupportedFamilyError)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def demo():
    cfg = get_config("lidc-demo")
    bundle = bundle_for(cfg)
    params = bundle.init(cfg, KEY)
    return cfg, params


def test_unsupported_family_raises_typed_error(demo):
    cfg, params = demo
    moe_cfg = dataclasses.replace(cfg, family="moe")
    with pytest.raises(UnsupportedFamilyError) as exc:
        ServeEngine(moe_cfg, params, max_batch=1, max_seq=32)
    assert exc.value.family == "moe"
    assert "moe" in str(exc.value)
    assert isinstance(exc.value, ValueError)     # typed but still a ValueError
    assert cfg.family in SUPPORTED_FAMILIES


def test_slot_exhaustion_with_nonempty_queue(demo):
    """More requests than slots: the queue drains as slots free, every
    request completes, and the batch never exceeds max_batch."""
    cfg, params = demo
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=64)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(list(rng.integers(0, cfg.vocab, 5)), max_new=4)
            for _ in range(6)]
    assert len(eng.queue) == 6 and all(s is None for s in eng.slots)
    done = eng.run()
    assert len(done) == 6 and all(r.done for r in reqs)
    assert all(len(r.out) == 4 for r in reqs)
    assert not eng.queue and all(s is None for s in eng.slots)


def test_eos_mid_batch_frees_slot_for_queued_request(demo):
    """A request finishing on EOS mid-batch hands its slot to a queued
    request without idle decode steps."""
    cfg, params = demo
    prompt = [3, 1, 4, 1, 5]
    # learn what greedy decode emits so we can make token #2 the EOS
    probe = ServeEngine(cfg, params, max_batch=1, max_seq=64)
    r = probe.submit(prompt, max_new=6)
    probe.run()
    eos = r.out[1]

    eng = ServeEngine(cfg, params, max_batch=1, max_seq=64)
    r1 = eng.submit(prompt, max_new=10, eos=eos)
    r2 = eng.submit([7, 8, 9], max_new=3)
    done = eng.run()
    assert [d.rid for d in done] == [r1.rid, r2.rid]
    assert r1.out[-1] == eos and len(r1.out) == 2   # stopped at EOS
    assert len(r2.out) == 3
    # no wasted steps: r1 took 1 decode step, r2 took its prefill + 2
    assert eng.decode_steps == 3


def test_eos_on_prefill_token_frees_slot_immediately(demo):
    cfg, params = demo
    prompt = [11, 12, 13]
    probe = ServeEngine(cfg, params, max_batch=1, max_seq=64)
    first = probe.submit(prompt, max_new=4)
    probe.run()
    eos = first.out[0]                      # the prefill-emitted token

    eng = ServeEngine(cfg, params, max_batch=1, max_seq=64)
    r = eng.submit(prompt, max_new=8, eos=eos)
    done = eng.run()
    assert done == [r] and r.out == [eos]
    assert eng.decode_steps == 0            # never entered the decode loop


def test_max_new_zero_finishes_without_slot(demo):
    cfg, params = demo
    eng = ServeEngine(cfg, params, max_batch=1, max_seq=32)
    r = eng.submit([1, 2, 3], max_new=0)
    assert r.done and r.out == [] and not eng.queue
    assert eng.run() == []
    assert eng.tokens_out == 0


def test_max_new_one_emits_exactly_one_token(demo):
    cfg, params = demo
    eng = ServeEngine(cfg, params, max_batch=1, max_seq=32)
    r = eng.submit([1, 2, 3], max_new=1)
    done = eng.run()
    assert done == [r] and len(r.out) == 1
    assert eng.decode_steps == 0


def test_priority_orders_admission(demo):
    cfg, params = demo
    eng = ServeEngine(cfg, params, max_batch=1, max_seq=32)
    lo = eng.submit([1, 2], max_new=2, priority=0)
    hi = eng.submit([3, 4], max_new=2, priority=5)
    done = eng.run()
    assert [d.rid for d in done] == [hi.rid, lo.rid]


def test_greedy_decode_survives_kv_checkpoint_restore(demo):
    """Checkpoint a mid-decode request, restore into a *fresh* engine,
    finish there: the token stream equals uninterrupted greedy decode."""
    cfg, params = demo
    prompt = [2, 7, 1, 8, 2, 8]
    max_new = 10

    solo = ServeEngine(cfg, params, max_batch=1, max_seq=64)
    want = solo.submit(prompt, max_new=max_new)
    solo.run()

    a = ServeEngine(cfg, params, max_batch=2, max_seq=64)
    r = a.submit(prompt, max_new=max_new)
    a._admit()
    for _ in range(3):                       # partway through decode
        a.step()
    assert 0 < len(r.out) < max_new
    state = a.kv_checkpoint(r)

    b = ServeEngine(cfg, params, max_batch=2, max_seq=64)
    restored = b.restore(state)
    assert restored.out == r.out             # picks up exactly where a was
    b.run()
    assert restored.done
    assert restored.out == want.out          # bit-identical to unbroken


def test_restore_rejects_when_full(demo):
    cfg, params = demo
    a = ServeEngine(cfg, params, max_batch=1, max_seq=64)
    r = a.submit([1, 2, 3], max_new=8)
    a._admit()
    a.step()
    state = a.kv_checkpoint(r)
    b = ServeEngine(cfg, params, max_batch=1, max_seq=64)
    b.submit([4, 5, 6], max_new=8)
    b._admit()                               # the only slot is now taken
    with pytest.raises(RuntimeError, match="no free slot"):
        b.restore(state)


def test_engine_through_kernels_matches_reference_logits():
    """A continuous batch through the Pallas kernels (interpret mode)
    reproduces the reference path's logits and tokens, for prompt lengths
    off the 128-token tile."""
    cfg = dataclasses.replace(smoke_of("qwen3-1.7b"), dtype="float32")
    params = bundle_for(cfg).init(cfg, KEY)
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab, n)) for n in (1, 200, 37)]
    runs = {}
    for impl in ("interpret", "ref"):
        with use_impl(impl):
            eng = ServeEngine(cfg, params, max_batch=2, max_seq=256,
                              record_logits=3)
            runs[impl] = [eng.submit(p, max_new=4) for p in prompts]
            eng.run()
    for got, want in zip(runs["interpret"], runs["ref"]):
        assert len(got.logits) == 3 and got.out == want.out
        np.testing.assert_allclose(np.stack(got.logits),
                                   np.stack(want.logits), atol=1e-4,
                                   rtol=1e-4)
