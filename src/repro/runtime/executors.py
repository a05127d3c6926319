"""Executors: the code a cluster runs when the gateway spawns a job.

Three applications, mirroring the paper's (BLAST + "any application"):

* ``train``  — real JAX training, phased with named checkpoints (failure
  mid-job loses at most one phase), when the job fits the executing
  device; otherwise the executor runs the calibrated cost model (the
  *virtual* durations follow the same roofline math the dry-run reports).
* ``serve``  — continuous-batching decode through the ServeEngine, under
  the same fit rule.
* ``blast``  — the paper's Table-I genomics workload: a real (small)
  Smith-Waterman alignment on synthetic reads, with run time scaled to the
  dataset, reproducing the cpu/mem (in)sensitivity the paper observed.

**Real or simulated.**  A job runs real compute on its cluster's device
(``ComputeCluster.device``, else the default device) when
:func:`~repro.models.model.memory_estimate` for it fits the device's
``memory_stats()["bytes_limit"]``.  A device that reports no limit (the
host CPU) runs real compute only below ``_REAL_PARAM_LIMIT`` parameters,
so production-size jobs stay simulated there.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..configs.base import ArchConfig, ShapeConfig, get_config, get_shape
from ..core.cluster import ComputeCluster, ExecPlan, ExecResult
from ..core.jobs import Job, JobSpec

__all__ = ["roofline_step_time", "make_train_executor", "ServeExecutor",
           "blast_executor", "memory_model",
           "smith_waterman", "runs_real"]

# TPU v5e constants (same as roofline/analysis.py)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ASSUMED_MFU = 0.4


def roofline_step_time(cfg: ArchConfig, shape: ShapeConfig, chips: int
                       ) -> float:
    """Virtual seconds per step from the analytic roofline (cost model)."""
    from ..models.model import model_flops, param_count
    flops = model_flops(cfg, shape)
    compute = flops / (chips * PEAK_FLOPS * ASSUMED_MFU)
    # memory term: weights + cache traffic once per step
    bytes_ = 2.0 * param_count(cfg, active_only=shape.kind == "decode")
    if shape.kind == "decode":
        bytes_ += 4.0 * cfg.n_kv_heads * cfg.hd * shape.seq_len \
            * shape.global_batch * cfg.n_layers
    memory = bytes_ / (chips * HBM_BW)
    return max(compute, memory, 1e-6)


def _prompt_lengths(fields: Dict[str, Any]) -> List[int]:
    """Serve job prompt lengths: ``plens=1,200,37`` (one request each),
    else ``requests=`` prompts of 8 tokens."""
    plens = str(fields.get("plens", ""))
    if plens:
        return [int(p) for p in plens.split(",")]
    return [8] * int(fields.get("requests", 4))


def _serve_shape(fields: Dict[str, Any]) -> ShapeConfig:
    """What a serve job holds: its requests' KV at their longest length."""
    plens = _prompt_lengths(fields)
    new_tokens = int(fields.get("new_tokens", 8))
    return ShapeConfig("serve", "decode", max(plens) + new_tokens, len(plens))


def memory_model(spec: JobSpec, chips: int) -> Optional[float]:
    """Matchmaker admission: estimated bytes/chip for a job."""
    from ..models.model import memory_estimate
    arch, shp = spec.arch, spec.shape
    if arch is None:
        return None
    try:
        cfg = get_config(arch)
        if shp:
            shape = get_shape(shp)
        elif spec.app == "serve":
            shape = _serve_shape(spec.fields)
        else:
            shape = ShapeConfig("d", "train", int(spec.fields.get("seq", 4096)),
                                int(spec.fields.get("batch", 256)))
    except (KeyError, ModuleNotFoundError):
        return None
    return memory_estimate(cfg, shape, chips)


def _resolve_arch(name: str) -> ArchConfig:
    from ..configs.base import registry, smoke_of
    if "smoke" in name:
        # "<arch_id>-smoke" or the smoke config's own id ("qwen3-smoke")
        for arch_id in registry():
            smoke = smoke_of(arch_id)
            if name in (arch_id + "-smoke", smoke.arch_id):
                return smoke
        raise KeyError(name)
    return get_config(name)


_REAL_PARAM_LIMIT = 50_000_000  # without a device limit: real below this


def _device_of(cluster: ComputeCluster):
    import jax
    return cluster.device if cluster.device is not None else jax.devices()[0]


def runs_real(cfg: ArchConfig, shape: ShapeConfig, device) -> bool:
    """Whether a job of ``cfg`` at ``shape`` runs real compute on
    ``device``: its one-chip memory estimate fits the device's byte limit;
    a device that reports no limit keeps the parameter-count rule."""
    from ..models.model import memory_estimate, param_count
    stats = device.memory_stats()
    limit = stats.get("bytes_limit") if stats else None
    if limit is None:
        return param_count(cfg) <= _REAL_PARAM_LIMIT
    return memory_estimate(cfg, shape, 1) <= limit


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_train_executor(*, ckpt_every: int = 10,
                        batch: int = 4, seq: int = 32) -> Callable:
    """``batch=``/``seq=`` job fields override the defaults given here."""
    def executor(job: Job, cluster: ComputeCluster):
        import jax
        from ..models.model import param_count
        cfg = _resolve_arch(job.spec.arch)
        steps = job.spec.steps(default=10)
        chips = max(job.granted_chips, 1)
        job_batch = int(job.spec.fields.get("batch", batch))
        job_seq = int(job.spec.fields.get("seq", seq))
        shape_name = job.spec.shape or "train_4k"
        try:
            shape = get_shape(shape_name)
        except KeyError:
            shape = ShapeConfig(shape_name, "train", job_seq, job_batch)
        step_time = roofline_step_time(cfg, shape, chips)
        run_name = f"train-{job.spec.signature()}"
        device = _device_of(cluster)
        lake = cluster.lake
        real = lake is not None and runs_real(
            cfg, ShapeConfig("run", "train", job_seq, job_batch), device)

        n_phases = max(1, math.ceil(steps / ckpt_every))
        losses: Dict[str, Any] = {"history": []}

        def phase_fn(phase_idx: int) -> Callable[[], None]:
            end_step = min((phase_idx + 1) * ckpt_every, steps)

            def work() -> None:
                if not real:
                    return  # simulated big-model job: time passes, no compute
                from ..train.trainer import run_training
                with jax.default_device(device):
                    res = run_training(cfg, steps=end_step, batch=job_batch,
                                       seq=job_seq, lake=lake,
                                       run_name=run_name,
                                       ckpt_every=ckpt_every, seed=0)
                losses["history"].extend(res.losses)
                if res.final_loss is not None:
                    losses["final"] = res.final_loss
                if res.resumed_from is not None:
                    losses.setdefault("resumed_from", res.resumed_from)

            return work

        phases = [(step_time * min(ckpt_every, steps - i * ckpt_every),
                   phase_fn(i)) for i in range(n_phases)]

        def finalize() -> ExecResult:
            payload = {
                "app": "train", "arch": cfg.arch_id, "steps": steps,
                "chips": chips, "step_time_s": step_time,
                "real_compute": real, "device": str(device),
                "run_name": run_name,
            }
            if losses.get("final") is not None:
                payload["final_loss"] = losses["final"]
                payload["losses"] = losses["history"]
                payload["resumed_from"] = losses.get("resumed_from")
            payload["output_bytes"] = 4 * int(param_count(cfg))
            return ExecResult(payload=payload, duration=0.0)

        return ExecPlan(phases=phases, finalize=finalize)

    return executor


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

class ServeExecutor:
    """Serve endpoint executor: one :class:`ServeEngine` per (arch, device).

    A job's fields give its requests: ``plens=`` prompt lengths (or
    ``requests=`` prompts of 8 tokens), ``new_tokens=`` per request and
    ``seed=`` for the prompt tokens.  Engines persist across jobs, so a
    model's weights (random, from seed 0) are made and its steps compiled
    once per device.  With ``record_logits=n`` each job's finished
    requests, with their first ``n`` logit rows, are kept in
    :attr:`finished` under the job id.
    """

    def __init__(self, *, max_batch: int = 4, max_seq: int = 64,
                 record_logits: int = 0):
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.record_logits = record_logits
        self.engines: Dict[Tuple[str, Any], Any] = {}
        self.finished: Dict[str, list] = {}

    def engine(self, cfg: ArchConfig, device):
        key = (cfg.arch_id, device)
        if key not in self.engines:
            import jax
            from jax.sharding import SingleDeviceSharding
            from ..models.model import bundle_for
            from ..serve.engine import ServeEngine
            bundle = bundle_for(cfg)
            params = jax.jit(lambda k: bundle.init(cfg, k),
                             out_shardings=SingleDeviceSharding(device))(
                jax.random.PRNGKey(0))
            self.engines[key] = ServeEngine(
                cfg, params, max_batch=self.max_batch, max_seq=self.max_seq,
                device=device, record_logits=self.record_logits)
        return self.engines[key]

    def __call__(self, job: Job, cluster: ComputeCluster) -> ExecResult:
        from ..serve.engine import SUPPORTED_FAMILIES
        cfg = _resolve_arch(job.spec.arch)
        fields = job.spec.fields
        plens = _prompt_lengths(fields)
        new_tokens = int(fields.get("new_tokens", 8))
        chips = max(job.granted_chips, 1)
        shape = ShapeConfig("serve", "decode", self.max_seq, self.max_batch)
        step_time = roofline_step_time(cfg, shape, chips)
        device = _device_of(cluster)
        real = cfg.family in SUPPORTED_FAMILIES \
            and runs_real(cfg, shape, device)
        if real:
            if max(plens) + new_tokens > self.max_seq:
                raise ValueError(f"prompt {max(plens)} + {new_tokens} new "
                                 f"tokens exceeds max_seq={self.max_seq}")
            eng = self.engine(cfg, device)
            rng = np.random.default_rng(int(fields.get("seed", 0)))
            before = eng.tokens_out
            for n in plens:
                eng.submit(list(rng.integers(0, cfg.vocab, n)),
                           max_new=new_tokens)
            done = eng.run()
            tokens = eng.tokens_out - before
            if self.record_logits:
                self.finished[job.job_id] = done
        else:
            tokens = len(plens) * new_tokens
        duration = step_time * max(tokens // self.max_batch, 1)
        return ExecResult(payload={"app": "serve", "arch": cfg.arch_id,
                                   "requests": len(plens),
                                   "tokens_out": tokens,
                                   "real_compute": real,
                                   "device": str(device),
                                   "output_bytes": 4 * tokens},
                          duration=duration)


# ---------------------------------------------------------------------------
# blast (the paper's own workload, Table I)
# ---------------------------------------------------------------------------

# (srr, db) -> (base run time seconds, output bytes); from paper Table I
_TABLE1 = {
    ("SRR2931415", "human"): (8 * 3600 + 9 * 60 + 50, 941 * 2 ** 20),
    ("SRR5139395", "human"): (24 * 3600 + 16 * 60 + 12,
                              int(2.71 * 2 ** 30)),
}


def smith_waterman(a: np.ndarray, b: np.ndarray) -> int:
    """Tiny real alignment kernel (the 'computation' behind the numbers).

    Shared with the workflow apps (repro.workflow.apps): align stages run
    the same kernel over data-lake shards."""
    n, m = len(a), len(b)
    H = np.zeros((n + 1, m + 1), np.int32)
    best = 0
    for i in range(1, n + 1):
        match = np.where(b == a[i - 1], 2, -1)
        for j in range(1, m + 1):
            h = max(0, H[i - 1, j - 1] + match[j - 1], H[i - 1, j] - 1,
                    H[i, j - 1] - 1)
            H[i, j] = h
            best = max(best, h)
    return int(best)


def blast_executor(job: Job, cluster: ComputeCluster) -> ExecResult:
    srr = str(job.spec.fields.get("srr"))
    db = str(job.spec.fields.get("db", "human"))
    mem = float(job.spec.fields.get("mem", 4))
    cpu = float(job.spec.fields.get("cpu", 2))
    base_time, out_bytes = _TABLE1.get(
        (srr, db), (3600.0, 100 * 2 ** 20))
    # The paper's own finding: cpu/mem variation barely moves run time
    # (I/O-bound) — model a 2% sensitivity, matching Table I deltas.
    duration = base_time * (1.0 - 0.01 * math.log2(max(cpu / 2, 1))
                            - 0.01 * math.log2(max(mem / 4, 1)))
    rng = np.random.default_rng(abs(hash((srr, db))) % 2 ** 31)
    score = smith_waterman(rng.integers(0, 4, 64), rng.integers(0, 4, 64))
    return ExecResult(payload={"app": "blast", "srr": srr, "db": db,
                               "mem": mem, "cpu": cpu,
                               "alignment_score": score,
                               "run_time_s": duration,
                               "output_bytes": out_bytes},
                      duration=duration)
