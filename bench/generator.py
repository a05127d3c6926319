"""The one traffic generator: turns a mix's data file into jobs.

Sizes are drawn as stratified quantiles of the distributions the mix
file states, so every seed gets the same sizes: the job-level sequence
(requests per job, answer length, train steps) follows a fixed
low-discrepancy order, and the seed only permutes the prompt lengths
inside each job and draws the job fields (hence the prompt tokens).
Runs on different seeds then do the same work in another order, and
their spread is the system's, not the generator's.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

from .common import derived_int, rng_for

# domains of the seed
_JOB_FIELDS, _PERMUTE, _WARMUP = 1, 2, 3


def radical_inverse(i: int, base: int) -> float:
    """Van der Corput sequence: a fixed, evenly spread order in (0, 1)."""
    f, r = 1.0, 0.0
    while i > 0:
        f /= base
        r += f * (i % base)
        i //= base
    return r


def lognormal_at(spec: Dict[str, Any], u: float) -> int:
    """Quantile ``u`` of a log-normal of the given median and sigma,
    clipped to ``[min, max]`` and put on the ``grid`` (if any)."""
    u = min(max(u, 1e-9), 1 - 1e-9)
    x = math.exp(math.log(spec["median"]) + spec["sigma"]
                 * NormalDist().inv_cdf(u))
    grid = int(spec.get("grid", 1))
    x = int(round(x / grid)) * grid
    return int(min(max(x, spec["min"]), spec["max"]))


def prompt_lengths(traffic: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix can draw: a job of ``n`` requests takes
    the ``n`` stratified quantiles of the prompt distribution."""
    spec, req = traffic["prompt_tokens"], traffic["requests_per_job"]
    return sorted({lognormal_at(spec, (i + 0.5) / n)
                   for n in range(int(req["min"]), int(req["max"]) + 1)
                   for i in range(n)})


def serve_job(traffic: Dict[str, Any], arch: str, seed: int, j: int
              ) -> Dict[str, Any]:
    """Fields of the ``j``-th serve job of a run."""
    n = lognormal_at(traffic["requests_per_job"], radical_inverse(j + 1, 2))
    new = lognormal_at(traffic["answer_tokens"], radical_inverse(j + 1, 3))
    plens = [lognormal_at(traffic["prompt_tokens"], (i + 0.5) / n)
             for i in range(n)]
    rng_for(seed, _PERMUTE, j).shuffle(plens)
    return {"app": "serve", "arch": arch,
            "plens": ",".join(str(p) for p in plens), "new_tokens": new,
            "seed": derived_int(seed, _JOB_FIELDS, j), "chips": 1}


def serve_warmup_jobs(traffic: Dict[str, Any], arch: str, seed: int
                      ) -> List[Dict[str, Any]]:
    """Jobs that touch every prompt length the mix can draw, and no other,
    and every engine slot, with fields no window job has."""
    slots = int(traffic["deployment"]["slots"])
    lens = prompt_lengths(traffic)
    plens = [lens[i % len(lens)] for i in range(max(slots, len(lens)))]
    return [{"app": "serve", "arch": arch,
             "plens": ",".join(str(p) for p in plens), "new_tokens": 2,
             "seed": derived_int(seed, _WARMUP, 0), "chips": 1}]


def expected_tokens(fields: Dict[str, Any], max_seq: int) -> int:
    """Tokens a serve job emits: ``new_tokens`` per request, cut where a
    request reaches ``max_seq - 1`` positions (the engine's limit)."""
    new = int(fields["new_tokens"])
    return sum(min(new, max_seq - 1 - int(p))
               for p in str(fields["plens"]).split(","))


def train_job(traffic: Dict[str, Any], arch: str, seed: int, j: int
              ) -> Dict[str, Any]:
    d = traffic["deployment"]
    return {"app": "train", "arch": arch, "steps": int(d["steps_per_job"]),
            "batch": int(d["batch"]), "seq": int(d["seq"]), "chips": 1,
            "seed": derived_int(seed, _JOB_FIELDS, j)}


def train_warmup_job(traffic: Dict[str, Any], arch: str, seed: int
                     ) -> Dict[str, Any]:
    fields = train_job(traffic, arch, seed, 0)
    fields["seed"] = derived_int(seed, _WARMUP, 0)
    return fields
