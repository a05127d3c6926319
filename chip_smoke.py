#!/usr/bin/env python3
"""Drive LIDC's model path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # four one-chip clusters, one host

One process, touching JAX once.  With no option it

1. prints the device JAX reports and the kernel implementation in use
   (``pallas`` on a TPU), and exits non-zero at once where the platform
   is not a TPU — there is no CPU fallback;
2. serves ``qwen3-1.7b`` at full width (random bf16 weights from seed 0)
   through the overlay: ``app=serve`` jobs are expressed through
   ``LidcClient.run_jobs``, so forwarder, gateway, scheduler and the serve
   executor drive the continuous-batching ``ServeEngine`` on the chip;
3. recomputes every served request's prefill logits and first decode-step
   logits with the jnp reference kernels on the same chip (feeding the
   tokens the server emitted) and fails above ``LOGIT_REL_TOL``;
4. trains ``qwen2-0.5b`` at full width for four steps as an ``app=train``
   job through the same overlay, with one named checkpoint in the lake;
5. prints ``{"ok": true, "device": {...}}`` as its last line.

With ``--chips 4`` it runs only the replica path instead: four one-chip
clusters, each bound to its own device, serve jobs until at least two
clusters have run one; each job's weights and KV cache must live on its
cluster's device, and its logits must match the same requests served on
device 0 alone.

Every failed check raises, so the script exits non-zero and prints no
result line.  The phase functions take their model names and sizes as
arguments, so the tests rehearse them at smoke size on the CPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SERVE_ARCH = "qwen3-1.7b"
TRAIN_ARCH = "qwen2-0.5b"
MAX_BATCH = 4               # continuous-batching slots
MAX_SEQ = 1024
NEW_TOKENS = 8
RECORD = 4                  # logit rows compared: prefill + 3 decode steps
# prompt lengths: 1, and 200/300 — above the 128-token tile, not multiples
# of it; five requests on four slots, so one waits for a slot to free
SERVE_JOBS = ("1,200,37,300,200", "200,1")
TRAIN_STEPS = 4
# 2 x 1024 tokens: the v5e compile of this step needs 11.7 GB of the 16 GB
# (1 GB bf16 weights, 4 GB fp32 AdamW moments, the rest activations, led
# by the 151,936-wide logits of each 512-token loss chunk)
TRAIN_BATCH = 2
TRAIN_SEQ = 1024

# bf16 keeps 8 significant bits (unit roundoff u = 2^-8).  The Pallas and
# reference paths round in different places — flash attention keeps the
# softmax probabilities in f32 and rescales online, the reference rounds
# them to bf16 before the PV product — so each of the L layers adds an
# independent O(u) relative error to the residual stream, which grows like
# sqrt(L)*u: about 2% for 28 layers.  The gate allows 2.5x that, relative
# to the largest reference logit of the row set.
LOGIT_REL_TOL = 0.05


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _memory(device) -> Any:
    """The device's own memory counters (``peak_bytes_in_use`` among them)."""
    return device.memory_stats() or "not reported"


def build_system(archs: Sequence[str], devices: Sequence[Any], serve_exec):
    """A LIDC overlay with one single-chip cluster per device, each
    advertising the standard train/serve endpoints for ``archs``."""
    from repro.core.overlay import LidcSystem
    from repro.runtime.executors import memory_model
    from repro.runtime.fleet import standard_endpoints

    sys_ = LidcSystem()
    for i, dev in enumerate(devices):
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        sys_.add_cluster(f"chip{i}", chips=1,
                         hbm_gb_per_chip=limit / 1e9 if limit else 16.0,
                         endpoints=standard_endpoints(
                             archs, ckpt_every=TRAIN_STEPS, serve=serve_exec),
                         memory_model=memory_model, device=dev)
    return sys_


def serve_fields(arch: str, plens: str, seed: int,
                 new_tokens: int = NEW_TOKENS) -> Dict[str, Any]:
    return {"app": "serve", "arch": arch, "plens": plens,
            "new_tokens": new_tokens, "seed": seed, "chips": 1}


def run_serve_jobs(sys_, fields_list: List[Dict[str, Any]]) -> list:
    """Express the jobs together; every one must complete on real compute."""
    handles = sys_.client.run_jobs(fields_list)
    for fields, h in zip(fields_list, handles):
        check(h is not None, f"no cluster answered {fields}")
        check(h.state == "Completed",
              f"serve job {h.job_id} {h.state}: {h.error}")
        res = h.result or {}
        check(res.get("real_compute") is True,
              f"serve job {h.job_id} did not run real compute: {res}")
        n = len(str(fields["plens"]).split(","))
        check(res.get("tokens_out") == n * fields["new_tokens"],
              f"serve job {h.job_id} emitted {res.get('tokens_out')} tokens")
        print(f"serve job {h.job_id}: cluster={res.get('cluster')} "
              f"device={res.get('device')} plens={fields['plens']} "
              f"tokens_out={res['tokens_out']}")
    return handles


def reference_logits(cfg, params, requests, max_seq: int) -> List[Any]:
    """Each request's prefill logits and first decode-step logits through
    the jnp reference kernels, fed the tokens the server emitted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ops import use_impl
    from repro.models import bundle_for

    bundle = bundle_for(cfg)
    out = []
    # the implementation is read at trace time: trace (and run) inside
    with use_impl("ref"):
        prefill = jax.jit(
            lambda p, t: bundle.prefill(cfg, p, t, max_seq=max_seq))
        decode = jax.jit(lambda p, c, t: bundle.decode_step(cfg, p, c, t))
        for req in requests:
            n = len(req.logits)
            logits, cache = prefill(params, jnp.asarray([req.prompt],
                                                        jnp.int32))
            rows = [logits[0, -1]]
            for tok in req.out[:n - 1]:
                logits, cache = decode(params, cache,
                                       jnp.asarray([[tok]], jnp.int32))
                rows.append(logits[0, 0])
            out.append(np.asarray(jnp.stack(rows), np.float32))
    return out


def compare_logits(got: Sequence[Any], want: Sequence[Any], what: str
                   ) -> Dict[str, float]:
    """Max-abs logit difference of prefill rows and of decode rows, gated
    at ``LOGIT_REL_TOL`` of the largest reference logit; greedy-token
    agreement is reported, not gated (random weights sit near ties)."""
    import numpy as np
    scale = max(float(np.max(np.abs(w))) for w in want)
    d_pre = max(float(np.max(np.abs(g[0] - w[0]))) for g, w in zip(got, want))
    d_dec = max((float(np.max(np.abs(g[1:] - w[1:])))
                 for g, w in zip(got, want) if len(w) > 1), default=0.0)
    agree = [int(np.argmax(a) == np.argmax(b))
             for g, w in zip(got, want) for a, b in zip(g, w)]
    finite = all(np.isfinite(g).all() for g in got)
    print(f"{what}: prefill max|dlogit|={d_pre} decode max|dlogit|={d_dec} "
          f"max|logit|={scale} tol={LOGIT_REL_TOL * scale} "
          f"greedy agreement={sum(agree)}/{len(agree)}")
    check(finite, f"{what}: non-finite logits")
    check(max(d_pre, d_dec) <= LOGIT_REL_TOL * scale,
          f"{what}: logits differ by {max(d_pre, d_dec)} > "
          f"{LOGIT_REL_TOL} x {scale}")
    return {"prefill": d_pre, "decode": d_dec, "scale": scale}


def serve_phase(sys_, serve_exec, arch: str, device,
                jobs: Sequence[str] = SERVE_JOBS) -> None:
    """Serve through the overlay, then check against the reference."""
    import numpy as np
    from repro.runtime.executors import _resolve_arch

    t0 = time.perf_counter()
    handles = run_serve_jobs(
        sys_, [serve_fields(arch, plens, seed) for seed, plens
               in enumerate(jobs, start=1)])
    print(f"serve wall seconds (compiles included): "
          f"{time.perf_counter() - t0}")
    cfg = _resolve_arch(arch)
    eng = serve_exec.engines[(cfg.arch_id, device)]
    requests = [r for h in handles for r in serve_exec.finished[h.job_id]]
    check(len(requests) == sum(len(j.split(",")) for j in jobs),
          f"{len(requests)} requests finished")
    check(any(len(r.prompt) > 128 and len(r.prompt) % 128 for r in requests)
          and any(len(r.prompt) == 1 for r in requests),
          "prompt lengths 1 and >128 (not a multiple of 128) were served")
    t0 = time.perf_counter()
    want = reference_logits(cfg, eng.params, requests, serve_exec.max_seq)
    print(f"reference wall seconds (compiles included): "
          f"{time.perf_counter() - t0}")
    compare_logits([np.stack(r.logits) for r in requests], want, f"{cfg.arch_id} pallas vs reference")
    print(f"memory after serving: {_memory(device)}")


def train_phase(sys_, arch: str, *, steps: int = TRAIN_STEPS,
                batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ) -> None:
    """Train through the overlay; losses finite, one checkpoint saved."""
    from repro.ckpt.checkpoint import latest_step

    fields = {"app": "train", "arch": arch, "steps": steps, "batch": batch,
              "seq": seq, "chips": 1}
    t0 = time.perf_counter()
    h = sys_.client.run_job(fields)
    check(h is not None, f"no cluster answered {fields}")
    check(h.state == "Completed", f"train job {h.job_id} {h.state}: {h.error}")
    res = h.result or {}
    print(f"train job {h.job_id}: cluster={res.get('cluster')} "
          f"device={res.get('device')} losses={res.get('losses')} "
          f"wall seconds (compile included)={time.perf_counter() - t0}")
    check(res.get("real_compute") is True,
          f"train job {h.job_id} did not run real compute: {res}")
    losses = res.get("losses") or []
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"train losses {losses}")
    check(latest_step(sys_.lake, res["run_name"]) == steps,
          "no named checkpoint at the last step")


def replica_phase(arch: str, devices: Sequence[Any], *, max_seq: int = MAX_SEQ,
                  plens: str = "1,200", max_rounds: int = 4) -> None:
    """Clusters as replicas: serve until two clusters have run a job, each
    on its own device, with logits equal to device 0 serving alone."""
    import jax
    import numpy as np
    from repro.runtime.executors import ServeExecutor, _resolve_arch

    cfg = _resolve_arch(arch)
    serve_exec = ServeExecutor(max_batch=MAX_BATCH, max_seq=max_seq,
                               record_logits=RECORD)
    sys_ = build_system([arch], devices, serve_exec)
    device_of = {f"chip{i}": d for i, d in enumerate(devices)}
    handles: list = []
    seed = 0
    for _ in range(max_rounds):
        batch = [serve_fields(arch, plens, seed + i)
                 for i in range(len(devices))]
        seed += len(devices)
        handles += run_serve_jobs(sys_, batch)
        if len({h.result["cluster"] for h in handles}) >= 2:
            break
    clusters = sorted({h.result["cluster"] for h in handles})
    check(len(clusters) >= 2, f"only {clusters} served")
    print(f"clusters that served: {clusters} on devices "
          f"{[str(device_of[c]) for c in clusters]}")

    solo = serve_exec.engine(cfg, devices[0])
    for h in handles:
        dev = device_of[h.result["cluster"]]
        eng = serve_exec.engines[(cfg.arch_id, dev)]
        placed = {d for a in jax.tree.leaves((eng.params, eng.cache))
                  for d in a.devices()}
        check(placed == {dev}, f"job {h.job_id} arrays on {placed}, "
                               f"not {dev}")
        reqs = serve_exec.finished[h.job_id]
        again = [solo.submit(r.prompt, max_new=r.max_new) for r in reqs]
        solo.run()
        compare_logits([np.stack(r.logits) for r in reqs],
                       [np.stack(r.logits) for r in again],
                       f"job {h.job_id} on {dev} vs device 0")


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-cluster replica path")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"the repro package is not beside {__file__}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import configure_compile_cache
    import jax
    from repro.kernels.ops import get_impl

    print(f"compile cache: {configure_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} kernels={get_impl()}")
    if dev.platform != "tpu":
        print(f"no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    if args.chips == 4:
        replica_phase(SERVE_ARCH, devices[:4])
    else:
        from repro.runtime.executors import ServeExecutor
        serve_exec = ServeExecutor(max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                                   record_logits=RECORD)
        sys_ = build_system([SERVE_ARCH, TRAIN_ARCH], [dev], serve_exec)
        serve_phase(sys_, serve_exec, SERVE_ARCH, dev)
        serve_exec.engines.clear()           # free the weights before training
        gc.collect()
        train_phase(sys_, TRAIN_ARCH)
        print(f"memory after training: {_memory(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
