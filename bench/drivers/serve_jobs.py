"""Serve jobs through the LIDC client, one closed loop.

Each cluster of the overlay is one chip (``device=jax.devices()[i]``)
advertising the standard endpoints, its serve endpoint backed by a
:class:`BenchServeExecutor`.  The client expresses ``app=serve`` jobs
(``LidcClient.run_jobs``, ``jobs_per_round`` at a time), so forwarder,
gateway, scheduler, executor, ``ServeEngine`` and the Pallas kernels are
the code under test.  The next round is expressed when the last one's
results are fetched.

Set-up has the program's executor build each chip's engine (its weights
come from the program's fixed key, ``PRNGKey(0)``) and warms every
prompt length the mix can draw and every engine slot.
The window then runs for ``--seconds``; rounds that begin inside it run
to their end, and only the tokens emitted inside it count.
"""

from __future__ import annotations

import collections
import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import generator
from bench.common import derived_int, limits_file, rng_for

_SAMPLE = 12
# ``ServeExecutor.engine`` draws every engine's weights from this key
PROGRAM_WEIGHT_SEED = 0


def make_executor(ctx, cfg, dep: Dict[str, Any]):
    """The program's ``ServeExecutor``, whose engines (built by the
    program) report their admissions and decode steps as host spans."""
    from repro.runtime.executors import ServeExecutor

    spans, log = ctx.spans, ctx.log

    class BenchServeExecutor(ServeExecutor):
        def engine(self, cfg, device):
            fresh = (cfg.arch_id, device) not in self.engines
            eng = super().engine(cfg, device)
            if fresh:
                instrument(eng, device)
            return eng

        def __call__(self, job, cluster):
            with spans.span("executor"):
                return super().__call__(job, cluster)

    def instrument(eng, device) -> None:
        step, admit = eng.step, eng._admit

        def timed_admit():
            queued = {id(r): r for r in eng.queue}
            before = eng.tokens_out
            with spans.span("engine_admit") as s:
                done = admit()
            new = [r for r in eng.slots if r is not None and id(r) in queued]
            new += [r for r in done if id(r) in queued]
            if new:
                log["admits"].append({"t1": s["t1"], "device": str(device),
                                      "prompts": [len(r.prompt) for r in new],
                                      "tokens": eng.tokens_out - before})
            log["finished"] += [(s["t1"], r) for r in done]
            return done

        def timed_step():
            active = [r for r in eng.slots if r is not None]
            before = eng.tokens_out
            with spans.span("engine_step") as s:
                done = step()
            if active:
                log["steps"].append({
                    "t0": s["t0"], "t1": s["t1"], "device": str(device),
                    # each active row's valid cache length in the kernel:
                    # its prompt, its tokens so far, the one being written
                    "lengths": [len(r.prompt) + len(r.out) for r in active],
                    "slots": eng.max_batch, "tokens": eng.tokens_out - before})
            log["finished"] += [(s["t1"], r) for r in done]
            ctx.tracer.maybe_stop()
            return done

        eng.step, eng._admit = timed_step, timed_admit

    return BenchServeExecutor(max_batch=int(dep["slots"]),
                              max_seq=int(dep["max_seq"]))


def build_system(arch: str, devices, executor):
    from repro.core.overlay import LidcSystem
    from repro.runtime.executors import memory_model
    from repro.runtime.fleet import standard_endpoints

    system = LidcSystem()
    for i, dev in enumerate(devices):
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        system.add_cluster(f"chip{i}", chips=1,
                           hbm_gb_per_chip=limit / 1e9 if limit else 16.0,
                           endpoints=standard_endpoints([arch],
                                                        serve=executor),
                           memory_model=memory_model, device=dev)
    return system


def run(ctx) -> Dict[str, Any]:
    import jax
    from repro.runtime.executors import _resolve_arch

    traffic, arch = ctx.traffic, ctx.config["arch"]
    dep = traffic["deployment"]
    devices = ctx.devices[:int(dep["clusters"])]
    per_round = int(dep["jobs_per_round"])
    max_seq = int(dep["max_seq"])
    cfg = _resolve_arch(arch)
    ctx.log.update(admits=[], steps=[], finished=[], jobs=[])

    # -- set-up: weights, then warm-up ------------------------------------
    executor = make_executor(ctx, cfg, dep)
    system = build_system(arch, devices, executor)
    with ctx.phase("weights"):
        for dev in devices:
            jax.block_until_ready(executor.engine(cfg, dev).params)
    with ctx.phase("warmup"):
        # every prompt length of the mix and every slot, on every engine,
        # through the calls the executor makes
        rng = rng_for(ctx.seed, _SAMPLE, 0)
        for fields in generator.serve_warmup_jobs(traffic, arch, ctx.seed):
            plens = [int(p) for p in fields["plens"].split(",")]
            for dev in devices:
                eng = executor.engine(cfg, dev)
                for n in plens:
                    eng.submit(list(rng.integers(0, cfg.vocab, n)),
                               max_new=fields["new_tokens"])
                eng.run()
        # and the overlay path once per cluster
        warm = [dict(generator.serve_job(traffic, arch, ctx.seed, 0),
                     seed=derived_int(ctx.seed, _SAMPLE, 1 + i),
                     plens=str(generator.prompt_lengths(traffic)[0]),
                     new_tokens=2)
                for i in range(len(devices))]
        system.client.run_jobs(warm)
    for key in ("admits", "steps", "finished"):
        ctx.log[key].clear()

    # -- the window ----------------------------------------------------------
    seconds = ctx.window_seconds
    t0 = ctx.start_window()
    j = 0
    while time.perf_counter() - t0 < seconds:
        batch = [generator.serve_job(traffic, arch, ctx.seed, j + i)
                 for i in range(per_round)]
        j += per_round
        t_expr = time.perf_counter()
        with ctx.spans.span("job"):
            handles = system.client.run_jobs(batch)
        t_done = time.perf_counter()
        for fields, h in zip(batch, handles):
            res = (h.result or {}) if h is not None else {}
            want = generator.expected_tokens(fields, max_seq)
            ok = (h is not None and h.state == "Completed"
                  and res.get("real_compute") is True
                  and res.get("tokens_out") == want)
            ctx.log["jobs"].append({
                "t_expr": t_expr, "t_done": t_done, "ok": ok,
                "requests": len(fields["plens"].split(",")),
                "tokens": res.get("tokens_out", 0),
                "cluster": res.get("cluster"),
                "state": None if h is None else h.state,
                "error": None if h is None else h.error})
    t_end = ctx.end_window(t0 + seconds)

    # -- what the window did ---------------------------------------------------
    log = ctx.log
    in_win = lambda t: t0 <= t <= t_end  # noqa: E731
    steps = [s for s in log["steps"] if in_win(s["t1"])]
    admits = [a for a in log["admits"] if in_win(a["t1"])]
    jobs = log["jobs"]
    job_spans = [s for s in ctx.spans.of("job") if s["t0"] >= t0]
    exec_spans = [s for s in ctx.spans.of("executor") if s["t0"] >= t0]
    record = {
        "window_s": t_end - t0,
        "chips": len(devices),
        "tokens": sum(s["tokens"] for s in steps)
        + sum(a["tokens"] for a in admits),
        "jobs_attempted": len(jobs),
        "jobs_failed": sum(not jb["ok"] for jb in jobs),
        "requests_attempted": sum(jb["requests"] for jb in jobs),
        "requests_completed": sum(jb["requests"] for jb in jobs if jb["ok"]),
        "job_latency_s": [jb["t_done"] - jb["t_expr"] for jb in jobs],
        "client_gap_s": [b["t_expr"] - a["t_done"] for a, b in
                         zip(jobs[::per_round], jobs[per_round::per_round])],
        "jobs_per_cluster": dict(collections.Counter(
            str(jb["cluster"]) for jb in jobs)),
        "overlay_s": [js["t1"] - js["t0"] - sum(
            e["t1"] - e["t0"] for e in exec_spans
            if js["t0"] <= e["t0"] and e["t1"] <= js["t1"])
            for js in job_spans],
        "steps": steps, "admits": admits,
        "model": ctx.config, "peaks": ctx.peaks,
    }
    step_ms = sorted(1e3 * (s["t1"] - s["t0"]) for s in steps) or [0.0]
    admit_s = sum(s["t1"] - s["t0"] for s in ctx.spans.of("engine_admit")
                  if in_win(s["t1"]))
    ctx.note(f"window: {record['tokens']} tokens; {len(steps)} decode "
             f"steps, ms median {step_ms[len(step_ms) // 2]} 90th "
             f"{step_ms[int(0.9 * (len(step_ms) - 1))]} max {step_ms[-1]}, "
             f"{sum(step_ms) / 1e3} s in all; {len(admits)} admissions "
             f"{admit_s} s")
    finished = [r for t, r in log["finished"] if in_win(t)]

    def verify(control: bool = False) -> List[Dict[str, Any]]:
        """The served tokens of a sample of the window's requests, the
        longest among them, against the float32 reference; with
        ``control`` the fp8 control's first choices in their place."""
        from bench.reference.dense import served_gaps
        executor.engines.clear()         # the program's state goes first
        gc.collect()
        limit = limits_file(ctx.cell["name"])["logit_gap"]["limit"]
        checks = [{"name": "jobs_failed", "value": record["jobs_failed"],
                   "limit": 0}]
        if not finished:
            checks.append({"name": "logit_gap", "value": float("inf"),
                           "limit": limit})
            return checks
        sample = sample_requests(finished, ctx.seed,
                                 int(traffic["check"]["requests"]))
        gaps = served_gaps(ctx.config, PROGRAM_WEIGHT_SEED,
                           [r.prompt for r in sample], [r.out for r in sample],
                           pad_to=max_seq,
                           n_pad=int(traffic["answer_tokens"]["max"]),
                           control=control)
        ctx.note(f"{'control: ' if control else ''}compared "
                 f"{sum(len(g) for g in gaps)} served tokens of "
                 f"{len(sample)} requests (prompts "
                 f"{[len(r.prompt) for r in sample]})")
        checks.append({"name": "logit_gap",
                       "value": float(max(float(np.max(g)) for g in gaps)),
                       "limit": limit})
        return checks

    return {"record": record, "verify": verify}


def sample_requests(finished, seed: int, n: int):
    """The longest request and ``n - 1`` others drawn from the seed."""
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i].prompt)
                                   + len(finished[i].out)))
    rest = order[1:]
    rng_for(seed, _SAMPLE, 2).shuffle(rest)
    return [finished[i] for i in [order[0]] + rest[:n - 1]]
