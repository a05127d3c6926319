"""The training driver: data -> steps -> named checkpoints -> results.

``run_training`` is used three ways:
* directly by examples/tests (real compute, small configs),
* by LIDC job executors (phased: checkpoint every k steps so a cluster
  failure mid-job loses at most one phase),
* by launch/train.py (the CLI entrypoint).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from .. import tracing
from ..configs.base import ArchConfig, ShapeConfig
from ..data.pipeline import make_pipeline
from ..optim.adamw import AdamW
from ..optim.schedule import warmup_cosine
from ..ckpt.checkpoint import (latest_step, restore_checkpoint,
                               save_checkpoint)
from .step import make_train_state, make_train_step

__all__ = ["TrainResult", "run_training"]


@dataclass
class TrainResult:
    run: str
    steps_done: int
    losses: List[float] = field(default_factory=list)
    resumed_from: Optional[int] = None
    wall_time: float = 0.0

    @property
    def final_loss(self) -> Optional[float]:
        return self.losses[-1] if self.losses else None


def run_training(cfg: ArchConfig, *, steps: int, batch: int = 8,
                 seq: int = 64, lake=None, run_name: str = "run",
                 ckpt_every: int = 0, seed: int = 0, lr: float = 3e-3,
                 remat: str = "none", microbatch: int = 1,
                 dataset: Optional[str] = None,
                 on_step: Optional[Callable[[int, float], None]] = None,
                 stop_flag: Optional[Callable[[], bool]] = None
                 ) -> TrainResult:
    """Train for ``steps`` optimizer steps, checkpointing into the lake.

    Resumes from the latest named checkpoint of ``run_name`` if one exists
    (this is what makes jobs migrate across clusters)."""
    t0 = time.perf_counter()
    shape = ShapeConfig("custom", "train", seq, batch)
    optimizer = AdamW(lr=warmup_cosine(lr, max(steps // 20, 2), steps))
    resumed_from = None
    start_step = 0
    with tracing.span("train.init"):
        state = make_train_state(cfg, jax.random.PRNGKey(seed), optimizer)
        if lake is not None and ckpt_every > 0:
            last = latest_step(lake, run_name)
            if last is not None and last > 0:
                state, start_step = restore_checkpoint(lake, run_name, state)
                resumed_from = start_step

    jitted = jax.jit(make_train_step(cfg, optimizer, remat=remat,
                                     microbatch=microbatch),
                     donate_argnums=0)
    step_fn = None
    pipeline = make_pipeline(cfg, shape, lake=lake, dataset=dataset,
                             seed=seed)
    it = iter(pipeline)

    result = TrainResult(run=run_name, steps_done=start_step,
                         resumed_from=resumed_from)
    for step in range(start_step, steps):
        if stop_flag is not None and stop_flag():
            break
        batch_dev = jax.tree.map(jnp.asarray, next(it))
        if step_fn is None:
            # traced and compiled (or loaded from the compile cache) here,
            # so no step's execution falls inside the build
            with tracing.span("train.build"):
                step_fn = jitted.lower(state, batch_dev).compile()
        with tracing.span("train.step", step=step):
            state, metrics = step_fn(state, batch_dev)
            with tracing.span("train.sync"):
                loss = float(metrics["loss"])
            result.losses.append(loss)
            result.steps_done = step + 1
            if on_step is not None:
                on_step(step, loss)
        if (lake is not None and ckpt_every > 0
                and (step + 1) % ckpt_every == 0):
            save_checkpoint(lake, run_name, step + 1, state,
                            meta={"loss": loss})
    if (lake is not None and ckpt_every > 0 and result.steps_done > start_step
            and result.steps_done % ckpt_every):      # not saved in the loop
        save_checkpoint(lake, run_name, result.steps_done, state,
                        meta={"loss": result.final_loss})
    result.wall_time = time.perf_counter() - t0
    return result
