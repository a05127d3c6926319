"""Fleet assembly: wire archs + executors into a multi-cluster LIDC overlay.

One call builds the paper's deployment at any scale: N clusters, each with
train/serve/blast endpoints for the architectures it hosts, all announced
into the overlay — plus the fault-tolerance utilities (failure injection,
straggler duplication via the multicast strategy, resilient client loop).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence

from ..configs.base import SHAPES, registry
from ..core.matchmaker import ServiceEndpoint
from ..core.overlay import LidcSystem
from ..core.strategy import Strategy
from .executors import (ServeExecutor, blast_executor, make_train_executor,
                        memory_model)

__all__ = ["build_fleet", "resilient_run", "standard_endpoints"]


def standard_endpoints(archs: Sequence[str], *, ckpt_every: int = 10,
                       serve: Optional[ServeExecutor] = None
                       ) -> List[ServiceEndpoint]:
    shapes = tuple(SHAPES) + ("custom",)
    return [
        ServiceEndpoint(service="train-lm.lidck8s.svc.cluster.local",
                        app="train", archs=tuple(archs), shapes=shapes,
                        executor=make_train_executor(ckpt_every=ckpt_every)),
        ServiceEndpoint(service="serve-lm.lidck8s.svc.cluster.local",
                        app="serve", archs=tuple(archs), shapes=shapes,
                        executor=serve or ServeExecutor()),
        ServiceEndpoint(service="magicblast.lidck8s.svc.cluster.local",
                        app="blast", executor=blast_executor),
    ]


def build_fleet(n_clusters: int = 3, *, chips: int = 256,
                archs: Optional[Sequence[str]] = None,
                latencies: Optional[Sequence[float]] = None,
                strategy: Optional[Strategy] = None,
                ckpt_every: int = 10) -> LidcSystem:
    """A LIDC overlay with ``n_clusters`` identical TPU pods."""
    archs = list(archs) if archs is not None else list(registry())
    archs += [a + "-smoke" for a in list(archs)] + ["lidc-demo"]
    sys_ = LidcSystem(strategy=strategy)
    for i in range(n_clusters):
        lat = latencies[i] if latencies else 0.002 * (i + 1)
        sys_.add_cluster(f"pod{i}", chips=chips, latency=lat,
                         endpoints=standard_endpoints(archs,
                                                      ckpt_every=ckpt_every),
                         memory_model=memory_model)
    return sys_


def resilient_run(sys_: LidcSystem, fields: Dict, *, max_attempts: int = 4,
                  poll_interval: float = 1.0):
    """Submit a job and drive it to completion across failures.

    Each attempt is the plain client workflow; if the serving cluster dies
    mid-run (status polls time out / job never completes), the client
    re-expresses the *same canonical name* — the overlay routes it to a
    surviving cluster, which resumes from the named checkpoint.
    """
    last = None
    for attempt in range(max_attempts):
        handle = sys_.client.run_job(fields, interval=poll_interval)
        last = handle
        if handle is not None and handle.state == "Completed":
            return handle, attempt + 1
    return last, max_attempts
