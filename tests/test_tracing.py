"""Program spans (repro.tracing): off by default, and where they sit in
the serve engine, the train driver, the checkpoint and the compute
plane when on."""

import jax
import numpy as np
import pytest

from repro import tracing
from repro.ckpt.checkpoint import ckpt_prefix
from repro.configs.base import smoke_of
from repro.datalake.lake import DataLake
from repro.models import bundle_for
from repro.runtime.fleet import build_fleet
from repro.serve.engine import ServeEngine
from repro.train.trainer import run_training

ARCH = "lidc-demo"


@pytest.fixture
def traced():
    """Tracing on for one test; off and empty afterwards."""
    tracing.drain()
    tracing.enable()
    yield
    tracing.disable()
    tracing.drain()


@pytest.fixture(scope="module")
def smoke_engine_parts():
    cfg = smoke_of(ARCH)
    return cfg, bundle_for(cfg).init(cfg, jax.random.PRNGKey(0))


def by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def test_off_records_nothing_and_returns_one_shared_object():
    assert not tracing.enabled()
    first = tracing.span("a", x=1)
    assert tracing.span("b") is first
    with tracing.span("c", job="j") as rec:
        assert rec is None
    assert tracing.now() is None
    assert tracing.drain() == []


def test_on_records_nesting_clock_and_attrs(traced):
    with tracing.span("outer", job="j1") as outer:
        with tracing.span("inner", rid=3) as inner:
            inner["count"] = 7
        with tracing.span("inner"):
            pass
    spans = tracing.drain()
    assert [s["name"] for s in spans] == ["inner", "inner", "outer"]
    assert outer["parent"] is None and outer["job"] == "j1"
    assert all(s["parent"] == outer["id"] for s in spans[:2])
    assert len({s["id"] for s in spans}) == 3
    assert spans[0]["rid"] == 3 and spans[0]["count"] == 7
    assert outer["t0"] <= spans[0]["t0"] <= spans[0]["t1"] <= outer["t1"]
    assert tracing.drain() == []


def test_disable_mid_span_keeps_the_open_span(traced):
    with tracing.span("open"):
        tracing.disable()
        assert tracing.span("after") is tracing.span("again")
    assert [s["name"] for s in tracing.drain()] == ["open"]


def test_engine_spans_per_request_and_step(traced, smoke_engine_parts):
    cfg, params = smoke_engine_parts
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=32)
    rng = np.random.default_rng(0)
    tracing.disable()
    early = eng.submit(list(rng.integers(0, cfg.vocab, 6)), max_new=3)
    tracing.enable()
    reqs = [early] + [eng.submit(list(rng.integers(0, cfg.vocab, n)),
                                 max_new=m)
                      for n, m in ((5, 4), (9, 1), (7, 5))]
    actives = []
    step = eng.step

    def counted_step():
        actives.append(sum(s is not None for s in eng.slots))
        return step()

    eng.step = counted_step
    done = eng.run()
    assert len(done) == len(reqs)
    spans = tracing.drain()

    prefills = by_name(spans, "serve.prefill")
    assert sorted(s["rid"] for s in prefills) == sorted(r.rid for r in reqs)
    assert {s["rid"]: s["prompt"] for s in prefills} == {
        r.rid: len(r.prompt) for r in reqs}
    for s in prefills:
        if s["rid"] == early.rid:       # submitted before tracing was on
            assert "queued_s" not in s
        else:
            assert s["queued_s"] >= 0.0
    admits = by_name(spans, "serve.admit")
    admit_ids = {s["id"] for s in admits}
    assert all(s["parent"] in admit_ids for s in prefills)

    steps = by_name(spans, "serve.step")
    assert len(steps) == eng.decode_steps
    assert [s["active"] for s in steps] == [a for a in actives if a]
    assert all(s["slots"] == 2 for s in steps)
    syncs = by_name(spans, "serve.sync")
    parents = {s["id"]: s["name"] for s in steps + prefills}
    assert len(syncs) == len(steps) + len(prefills)
    assert all(parents[s["parent"]] in ("serve.step", "serve.prefill")
               for s in syncs)
    assert sum(parents[s["parent"]] == "serve.step"
               for s in syncs) == len(steps)
    # every decode token came from a step of that many active slots
    assert sum(s["active"] for s in steps) == eng.tokens_out - len(reqs)


def test_engine_output_is_the_same_with_tracing_on(smoke_engine_parts):
    cfg, params = smoke_engine_parts
    outs = []
    for on in (False, True):
        if on:
            tracing.enable()
        try:
            eng = ServeEngine(cfg, params, max_batch=2, max_seq=32)
            rng = np.random.default_rng(1)
            reqs = [eng.submit(list(rng.integers(0, cfg.vocab, 6)),
                               max_new=4) for _ in range(3)]
            eng.run()
            outs.append([r.out for r in reqs])
        finally:
            tracing.disable()
            tracing.drain()
    assert outs[0] == outs[1]


def test_training_spans_and_losses_unchanged(traced):
    cfg = smoke_of(ARCH)
    kw = dict(steps=4, batch=2, seq=16, ckpt_every=3, seed=0)
    lake = DataLake()
    res = run_training(cfg, lake=lake, run_name="traced", **kw)
    spans = tracing.drain()
    tracing.disable()
    plain = run_training(cfg, lake=DataLake(), run_name="plain", **kw)
    assert tracing.drain() == []
    assert res.losses == plain.losses            # bit-equal floats

    assert len(by_name(spans, "train.init")) == 1
    assert len(by_name(spans, "train.build")) == 1
    steps = by_name(spans, "train.step")
    assert [s["step"] for s in steps] == [0, 1, 2, 3]
    syncs = by_name(spans, "train.sync")
    assert sorted(s["parent"] for s in syncs) == sorted(s["id"]
                                                        for s in steps)
    build = by_name(spans, "train.build")[0]
    assert build["t1"] <= steps[0]["t0"]

    saves = by_name(spans, "ckpt.save")
    assert [s["step"] for s in saves] == [3, 4]   # every 3, and the end
    for s in saves:
        arrays = lake.get_arrays(ckpt_prefix("traced").append(
            f"step={s['step']}"))
        assert s["bytes"] == sum(a.nbytes for a in arrays.values())
        kids = [k["name"] for k in spans if k["parent"] == s["id"]]
        assert kids == ["ckpt.device_get", "lake.put"]


def test_resumed_training_restores_inside_init(traced):
    cfg = smoke_of(ARCH)
    lake = DataLake()
    kw = dict(batch=2, seq=16, ckpt_every=2, lake=lake, run_name="r")
    run_training(cfg, steps=2, **kw)
    tracing.drain()
    res = run_training(cfg, steps=2, **kw)
    assert res.resumed_from == 2 and res.losses == []
    names = [s["name"] for s in tracing.drain()]
    assert names == ["train.init"]          # nothing left to build or run


def test_lidc_job_spans_nest_under_run_jobs(traced):
    system = build_fleet(n_clusters=1, chips=8, archs=[ARCH], ckpt_every=2)
    h_serve, h_train = system.client.run_jobs([
        {"app": "serve", "arch": ARCH + "-smoke", "plens": "5,9",
         "new_tokens": 3},
        {"app": "train", "arch": ARCH + "-smoke", "shape": "custom",
         "chips": 4, "steps": 4, "batch": 2, "seq": 16}])
    assert h_serve.state == h_train.state == "Completed"
    spans = tracing.drain()
    runs = by_name(spans, "lidc.run_jobs")
    assert len(runs) == 1 and runs[0]["jobs"] == 2
    execs = by_name(spans, "lidc.exec")
    assert all(s["parent"] == runs[0]["id"] for s in execs)
    serve = [s for s in execs if s["job"] == h_serve.job_id]
    train = [s for s in execs if s["job"] == h_train.job_id]
    # the serve call; the train executor's plan, its two checkpointed
    # phases and its finalize
    assert len(serve) == 1 and len(train) == 4
    assert len(serve) + len(train) == len(execs)
    exec_of = {s["id"]: s["job"] for s in execs}
    by_id = {s["id"]: s for s in spans}

    def job_of(s):
        while s["parent"] is not None and s["parent"] not in exec_of:
            s = by_id[s["parent"]]
        return exec_of.get(s["parent"])

    assert {job_of(s) for s in by_name(spans, "serve.prefill")} == {
        h_serve.job_id}
    assert {job_of(s) for s in by_name(spans, "train.step")} == {
        h_train.job_id}
    assert len(by_name(spans, "train.step")) == 4
