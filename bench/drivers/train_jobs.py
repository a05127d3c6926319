"""Named train jobs through the LIDC client, one after another.

One cluster (one chip) advertises the standard endpoints with named
checkpoints every ``steps_per_job`` steps, so each ``app=train`` job is
a fresh run of that many steps (state init, the jitted train step, the
synchronous host checkpoint into the lake) as the program's train
executor runs it.  Every job has fields no other job has, so none is
answered from the result cache.

Set-up runs one warm-up job.  In the window the next job is expressed
while the clock is inside ``--seconds``; the window closes at the last
completion.  A completed job's checkpoint is
dropped from the lake once read, so host memory stays flat.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import generator
from bench.common import limits_file


def _checkpoint(lake, run_name: str, drop: bool):
    """A run's last checkpoint: removed from the lake (``drop``), or read
    as flat arrays."""
    from repro.ckpt.checkpoint import ckpt_prefix, latest_step
    prefix = str(ckpt_prefix(run_name))
    if drop:
        for key in [k for k in lake.store.keys() if k.startswith(prefix)]:
            lake.store.delete(key)
        return None
    step = latest_step(lake, run_name)
    return lake.get_arrays(ckpt_prefix(run_name).append(f"step={step}"))


def run(ctx) -> Dict[str, Any]:
    from repro.core.overlay import LidcSystem
    from repro.runtime.executors import memory_model
    from repro.runtime.fleet import standard_endpoints

    traffic, arch = ctx.traffic, ctx.config["arch"]
    dep = traffic["deployment"]
    steps, batch, seq = (int(dep[k]) for k in ("steps_per_job", "batch", "seq"))
    dev = ctx.devices[0]
    system = LidcSystem()
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    system.add_cluster("chip0", chips=1,
                       hbm_gb_per_chip=limit / 1e9 if limit else 16.0,
                       endpoints=standard_endpoints([arch], ckpt_every=steps),
                       memory_model=memory_model, device=dev)
    jobs: List[Dict[str, Any]] = []

    def one_job(fields, keep: bool = False) -> Dict[str, Any]:
        """One job, its checkpoint dropped from the lake unless ``keep``."""
        t_expr = time.perf_counter()
        with ctx.spans.span("train_job"):
            h = system.client.run_job(fields)
        t_done = time.perf_counter()
        res = (h.result or {}) if h is not None else {}
        losses = res.get("losses") or []
        ok = (h is not None and h.state == "Completed"
              and res.get("real_compute") is True and len(losses) == steps
              and all(np.isfinite(losses)))
        ctx.tracer.maybe_stop()
        if ok and not keep:
            _checkpoint(system.lake, res["run_name"], drop=True)
        return {"t_expr": t_expr, "t_done": t_done, "ok": ok,
                "losses": losses, "run_name": res.get("run_name"),
                "state": None if h is None else h.state,
                "error": None if h is None else h.error}

    with ctx.phase("warmup"):
        one_job(generator.train_warmup_job(traffic, arch, ctx.seed))

    seconds = ctx.window_seconds
    t0 = ctx.start_window()
    j = 0
    while time.perf_counter() < t0 + seconds:
        jobs.append(one_job(generator.train_job(traffic, arch, ctx.seed, j),
                            keep=j == 0))      # the first job's state is compared
        j += 1
    t_end = ctx.end_window(jobs[-1]["t_done"])
    done = [jb for jb in jobs if jb["ok"]]
    record = {
        "window_s": t_end - t0, "chips": 1,
        "train_tokens": len(done) * steps * batch * seq,
        "train_steps": len(done) * steps, "batch": batch, "seq": seq,
        "jobs_attempted": len(jobs),
        "jobs_failed": sum(not jb["ok"] for jb in jobs),
        "job_latency_s": [jb["t_done"] - jb["t_expr"] for jb in jobs],
        "client_gap_s": [b["t_expr"] - a["t_done"]
                         for a, b in zip(jobs, jobs[1:])],
        "model": ctx.config, "peaks": ctx.peaks,
    }
    ctx.note(f"train jobs: {len(jobs)} of {steps} steps; job seconds "
             f"{record['job_latency_s']}; losses {jobs[0]['losses']}")

    def verify(control=False) -> List[Dict[str, Any]]:
        """The window's losses, and the first job's parameter change and
        first moment after its steps, against the float32 reference run
        over the same batches.  With ``control`` the reference in fp8 is
        put in the program's place; with ``control="half_batch"`` the
        reference that leaves half of each batch out."""
        from bench.reference import train as ref
        gc.collect()
        limits = limits_file(ctx.cell["name"])
        t = time.perf_counter()
        want = ref.train(ctx.config, 0, 0, batch, seq, steps, steps)
        ctx.note(f"reference steps took {time.perf_counter() - t} s; "
                 f"losses {want['losses']}")
        keep = ref.moving_leaves(want["first_grad_norm"])
        left_out = sorted(set(want["first_grad_norm"]) - set(keep))
        if left_out:
            ctx.note(f"leaves left out (reference gradient under a "
                     f"thousandth of the median leaf's): {left_out}")
        if control:
            half = control == "half_batch"
            got = ref.train(ctx.config, 0, 0, batch, seq, steps, steps,
                            precision="f32" if half else "fp8",
                            rows=batch // 2 if half else None)
            losses = [got["losses"]]
        elif jobs[0]["ok"]:
            arrays = _checkpoint(system.lake, jobs[0]["run_name"], drop=False)
            got = ref.norms_from_checkpoint(arrays, want["p0"])
            del arrays
            losses = [jb["losses"] for jb in done]
        else:
            got, losses = None, []
        checks = [{"name": "jobs_failed", "value": record["jobs_failed"],
                   "limit": 0}]
        inf = float("inf")
        checks.append({"name": "loss_gap", "value": max(
            (abs(a - b) for ls in losses for a, b in zip(ls, want["losses"])),
            default=inf), "limit": limits["loss_gap"]["limit"]})
        for name in ("update", "moment"):
            key = name + "_norm"
            gap = (max(ref.leaf_gap(got[key], want[key], keep).values())
                   if got else inf)
            checks.append({"name": name + "_gap", "value": gap,
                           "limit": limits[name + "_gap"]["limit"]})
        return checks

    return {"record": record, "verify": verify}
