"""Tests of the benchmark harness, on the CPU at smoke size.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench

They check ``BENCHMARK.json`` against the files it names, the traffic
generator, the FLOP and byte counts, the trace reduction (on a trace
recorded on the chip), the float32 reference against the program, both
drivers end to end through ``LidcSystem``, the control and the planted
faults (``correct`` must come out false), and that ``bench/run.py``
refuses a machine without a TPU.
"""

from __future__ import annotations

import copy
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
TESTS = os.path.join(ROOT, "tests", "bench")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common, flops, generator, run, trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = common.load_benchmark()


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


# -- BENCHMARK.json ----------------------------------------------------------

@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_resolve_by_name(cell):
    config = common.config_file(BENCH, cell["config"])
    assert config["name"] == cell["config"]
    traffic = common.traffic_file(cell["traffic"])
    common.load_module("drivers", traffic["driver"])
    common.limits_file(cell["name"])
    for trace_run in (False, True):
        for m in common.cell_metrics(BENCH, cell["name"], trace_run):
            assert hasattr(common.load_module("metrics", m["name"]), "read")


def test_names_units_and_keys():
    for group in (_metrics(), BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(NAME.match(w["traffic"]) for w in BENCH["workloads"])
    for m in _metrics():
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        cfg = common.load_json(os.path.join(ROOT, c["file"]))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))


def test_moves_is_reported_where_the_metric_is():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        reported = common.cell_metrics(BENCH, cell, False)
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert common.cell_metrics(BENCH, cell, True)


def test_at_most_half_the_cells_take_four_chips():
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4}
    assert sum(c == 4 for c in chips) <= max(1, len(chips) // 2)


# -- the traffic generator ---------------------------------------------------

SERVE_MIXES = sorted({w["traffic"] for w in BENCH["workloads"]
                      if common.traffic_file(w["traffic"])["driver"]
                      == "serve_jobs"} | {"smoke-chat"})


@pytest.fixture
def test_data_dirs(monkeypatch):
    monkeypatch.setattr(common, "DATA_DIRS", [TESTS, common.BENCH_DIR])


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_serve_traffic_is_seeded_gridded_clipped_and_unique(mix,
                                                            test_data_dirs):
    from repro.core.names import canonical_job_name
    traffic = common.traffic_file(mix)
    p, a = traffic["prompt_tokens"], traffic["answer_tokens"]
    seeds = (2 ** 31 + 12345, 3 ** 30)
    runs = {s: [generator.serve_job(traffic, "arch", s, j) for j in range(60)]
            for s in seeds}
    assert runs[seeds[0]] == [generator.serve_job(traffic, "arch", seeds[0], j)
                              for j in range(60)]
    names = set()
    for s in seeds:
        jobs = runs[s] + generator.serve_warmup_jobs(traffic, "arch", s)
        for f in jobs:
            plens = [int(x) for x in f["plens"].split(",")]
            assert all(x % p["grid"] == 0 and p["min"] <= x <= p["max"]
                       for x in plens)
            assert (a["min"] <= f["new_tokens"] <= a["max"]
                    or f in generator.serve_warmup_jobs(traffic, "arch", s))
            names.add(str(canonical_job_name(f)))
    assert len(names) == sum(len(r) + 1 for r in runs.values())
    # every seed gets the same sizes, in another order
    for x, y in zip(*runs.values()):
        assert sorted(x["plens"].split(",")) == sorted(y["plens"].split(","))
        assert x["new_tokens"] == y["new_tokens"]
    # the warm-up covers every length the mix draws, and no other
    warm = generator.serve_warmup_jobs(traffic, "arch", seeds[0])[0]
    drawn = {int(x) for s in seeds for f in runs[s]
             for x in f["plens"].split(",")}
    assert {int(x) for x in warm["plens"].split(",")} == drawn


def test_serve_jobs_fit_the_admission_model():
    """The program admits a serve job by the KV of all its requests at
    once; the largest job a mix can draw must fit one v5e chip."""
    from repro.configs.base import get_config
    from repro.core.jobs import JobSpec
    from repro.runtime.executors import memory_model
    limit = 16909336064          # bytes_limit a v5e chip reports
    for w in BENCH["workloads"]:
        traffic = common.traffic_file(w["traffic"])
        if traffic["driver"] != "serve_jobs":
            continue
        arch = common.config_file(BENCH, w["config"])["arch"]
        get_config(arch)
        n = traffic["requests_per_job"]["max"]
        fields = {"app": "serve", "arch": arch, "chips": 1, "seed": 1,
                  "plens": ",".join([str(traffic["prompt_tokens"]["max"])] * n),
                  "new_tokens": traffic["answer_tokens"]["max"]}
        spec = JobSpec(app="serve", fields=fields)
        assert memory_model(spec, 1) <= limit, w["name"]


def test_train_traffic_unique_fields():
    traffic = common.traffic_file("train-ckpt")
    fields = [generator.train_job(traffic, "a", 2 ** 33 + 5, j)
              for j in range(20)]
    fields.append(generator.train_warmup_job(traffic, "a", 2 ** 33 + 5))
    assert len({f["seed"] for f in fields}) == len(fields)
    assert fields[0] == generator.train_job(traffic, "a", 2 ** 33 + 5, 0)


# -- FLOPs and bytes ---------------------------------------------------------

def test_flops_by_hand():
    q3 = common.config_file(BENCH, "qwen3-1.7b")
    # per layer: 4*2048*2048 (q, o) + 4*2048*1024 (k, v) + 6*2048*6144 (mlp)
    assert flops.layer_matmul_flops(q3) == 100_663_296
    assert flops.head_flops(q3) == 2 * 2048 * 151_936
    assert flops.decode_flops(q3, 1) == (28 * (100_663_296 + 4 * 16 * 128)
                                         + 622_329_856)
    assert flops.prefill_flops(q3, 4) == (28 * (4 * 100_663_296
                                                + 4 * 16 * 128 * 10)
                                          + 622_329_856)
    q2 = json.load(open(os.path.join(ROOT, "bench/configs/qwen2-0.5b.json")))
    assert flops.layer_matmul_flops(q2) == 29_818_880
    assert flops.train_step_flops(q2, 2, 1024) == 3 * (
        24 * (2048 * 29_818_880 + 4 * 14 * 64 * 1_049_600)
        + 2048 * 2 * 896 * 151_936)


def test_decode_work_counts_valid_lengths_not_smax():
    q3 = common.config_file(BENCH, "qwen3-1.7b")
    w = flops.flash_decode_work(q3, [100, 2000])
    assert w["bytes"] == (2100 * 2 * 8 * 128 * 2) + 2 * 2 * 16 * 128 * 2
    assert w["flops"] == 4 * 16 * 128 * 2100
    # idle slots add nothing
    assert flops.flash_decode_work(q3, [100, 2000]) == w
    a = flops.flash_attention_work(q3, 1, 1024)
    assert a["flops"] == 4 * 16 * 128 * (1024 * 1025 // 2)


# -- the trace reduction -----------------------------------------------------

def test_reduction_of_the_recorded_chip_trace():
    data = os.path.join(ROOT, "bench", "testdata")
    want = common.load_json(os.path.join(data, "decode_steps.json"))
    raw = trace.read(os.path.join(data, "decode_steps.xplane.pb"))
    got = trace.reduce(raw, run.SPAN_NAMES, devices=[0])
    assert got["kernel_calls"] == want["kernel_calls"]
    # one flash_decode call per layer per decode step
    assert got["kernel_calls"]["flash_decode"] == 28 * want["decode_steps"]
    assert 0 < got["busy_s"] <= got["window_s"]
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    for k, v in want["kernel_s"].items():
        assert got["kernel_s"][k] == pytest.approx(v, rel=1e-9)
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    assert all(name in run.SPAN_NAMES + ("none",)
               for name, _ in got["idle_gaps"])


def test_self_times_and_kernel_names():
    ops = [(0, 10, "loop"), (1, 3, "a"), (4, 8, "b"), (12, 13, "a")]
    assert trace.self_times(ops) == {"loop": 4, "a": 3, "b": 4}
    assert trace.kernel_of("%flash_decode.4 = bf16[16,8,2,128]{3,2,1,0} "
                           "custom-call(s32[16]{0} %x)") == "flash_decode"
    assert trace.kernel_of("%copy.3 = bf16[2]{0} copy(bf16[2]{0} %x)") is None


# -- the reference against the program ----------------------------------------

@pytest.mark.parametrize("arch", ["qwen3-smoke", "qwen2-smoke"])
def test_reference_draws_the_programs_weights_and_agrees(arch):
    import jax
    import jax.numpy as jnp
    from bench.reference import dense, train as ref_train
    from repro.models.model import bundle_for
    from repro.runtime.executors import _resolve_arch
    cfg_file = common.load_json(os.path.join(TESTS, "configs", arch + ".json"))
    cfg = _resolve_arch(arch)
    m = dense.dims(cfg_file)
    prog = bundle_for(cfg).init(cfg, jax.random.PRNGKey(12345))
    mine = ref_train.init(m, 12345)
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(prog)[0]}
    assert sorted(flat) == sorted(mine)
    for k, v in mine.items():
        np.testing.assert_array_equal(np.asarray(flat[k], np.float32),
                                      np.asarray(v), err_msg=k)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, m["V"], (2, 40)),
                         jnp.int32)
    want = np.asarray(bundle_for(cfg).apply(cfg, prog, tokens), np.float32)
    with jax.default_matmul_precision("highest"):
        x, emb = dense.hidden(m, 12345, tokens)
        got = np.asarray(x @ emb.T)
    # the program computes in bfloat16: 8 significant bits over two layers
    assert np.max(np.abs(got - want)) < 0.02 * np.max(np.abs(got))
    assert np.mean(np.argmax(got, -1) == np.argmax(want, -1)) > 0.9


# -- the drivers end to end on the CPU ---------------------------------------

def _smoke_bench(config: str, traffic: str, e2e: str):
    """BENCHMARK.json with a smoke cell that reports ``e2e`` and setup_s."""
    bench = copy.deepcopy(BENCH)
    name = f"{config}.{traffic}"
    bench["configs"].append({"name": config, "source": "smoke",
                             "file": f"tests/bench/configs/{config}.json",
                             "reduced": [], "why": "smoke"})
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1, "why": "smoke"})
    metrics = {m["name"]: m for m in bench["end_to_end"]}
    if e2e not in metrics:
        bench["end_to_end"].append({"name": e2e, "unit": "tokens/s",
                                    "better": "higher", "bound": 0.05,
                                    "source": "host_clock", "workloads": []})
        metrics[e2e] = bench["end_to_end"][-1]
    metrics[e2e]["workloads"].append(name)
    return bench, name


def _run(bench, name, seed, control=False, seconds=2.0):
    args = run.parse(["--workload", name, "--seed", str(seed),
                      "--seconds", str(seconds)])
    code, res = run.run_cell(args, require_tpu=False, benchmark=bench,
                             control=control)
    assert code == 0
    return res


def test_serve_driver_smoke(test_data_dirs):
    bench, name = _smoke_bench("qwen3-smoke", "smoke-chat",
                               "serve_tokens_per_s")
    res = _run(bench, name, 2 ** 40 + 3, control=True)
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    # the control, in the program's place, fails the limit
    lim = res["checks"]["logit_gap"]["limit"]
    assert res["control_checks"]["logit_gap"] > lim


def test_train_driver_smoke(test_data_dirs):
    bench, name = _smoke_bench("qwen2-smoke", "smoke-train",
                               "train_tokens_per_s")
    res = _run(bench, name, 5, control=True)
    assert res["correct"] is True, res
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert res["failed"] == 0
    assert any(res["control_checks"][k] > res["checks"][k]["limit"]
               for k in ("loss_gap", "update_gap", "moment_gap"))


def test_a_token_altered_where_produced_is_not_correct(test_data_dirs,
                                                       monkeypatch):
    from repro.serve.engine import ServeEngine
    step = ServeEngine.step

    def altered(self):
        done = step(self)
        for req in [r for r in self.slots if r is not None] + done:
            if len(req.out) == 3:
                req.out[-1] = (req.out[-1] + 1) % self.cfg.vocab
        return done

    monkeypatch.setattr(ServeEngine, "step", altered)
    bench, name = _smoke_bench("qwen3-smoke", "smoke-chat",
                               "serve_tokens_per_s")
    assert _run(bench, name, 11)["correct"] is False


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_train_faults_are_not_correct(fault, test_data_dirs, monkeypatch):
    import repro.train.trainer as trainer
    make = trainer.make_train_step

    def broken(cfg, optimizer, **kw):
        step = make(cfg, optimizer, **kw)

        def faulty(state, batch):
            if fault == "half_batch":
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return step(state, half)
            new, metrics = step(state, batch)
            return {"params": state["params"], "opt": new["opt"]}, metrics

        return faulty

    monkeypatch.setattr(trainer, "make_train_step", broken)
    bench, name = _smoke_bench("qwen2-smoke", "smoke-train",
                               "train_tokens_per_s")
    assert _run(bench, name, 9)["correct"] is False


# -- the command --------------------------------------------------------------

def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        BENCH["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().splitlines()[-1:] or \
        not p.stdout.strip().splitlines()[-1].startswith("{")


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        BENCH["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
