"""Shared pieces of the harness: files found by name, seeds, host spans
and the compile counter.

Nothing here touches a device; importing it imports no JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# where traffic mixes and limits are looked up; the tests add their own
DATA_DIRS = [BENCH_DIR]


class BenchError(RuntimeError):
    """The benchmark cannot run as asked (missing file, no chip, ...)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Optional[str] = None) -> Dict[str, Any]:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def cell_of(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def config_file(bench: Dict[str, Any], config: str) -> Dict[str, Any]:
    for c in bench["configs"]:
        if c["name"] == config:
            return load_json(os.path.join(ROOT, c["file"]))
    raise BenchError(f"no configuration {config!r} in BENCHMARK.json")


def _data(kind: str, name: str) -> Dict[str, Any]:
    for d in DATA_DIRS:
        path = os.path.join(d, kind, name + ".json")
        if os.path.isfile(path):
            return load_json(path)
    raise BenchError(f"no {kind} file {name}.json")


def traffic_file(traffic: str) -> Dict[str, Any]:
    return _data("traffic", traffic)


def limits_file(cell: str) -> Dict[str, Any]:
    """The limits of a cell's compared numbers and the readings they were
    set from."""
    return _data("limits", cell)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: Dict[str, Any], cell: str, trace: bool
                 ) -> List[Dict[str, Any]]:
    """The metrics a cell reports: end-to-end ones with ``--trace 0``,
    per-layer ones with ``--trace 1``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def rng_for(seed: int, *domain: int) -> np.random.Generator:
    """An independent generator for ``seed`` (any size) and a domain."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *domain])


def derived_int(seed: int, *domain: int, bits: int = 62) -> int:
    return int(rng_for(seed, *domain).integers(0, 2 ** bits))


# ---------------------------------------------------------------------------
# host spans and counters
# ---------------------------------------------------------------------------

class SpanLog:
    """Host spans of one run.  Each span is also a profiler
    ``TraceAnnotation``, so in a traced window the device's idle gaps can
    be named by the host span open at the time."""

    def __init__(self) -> None:
        self.spans: List[Dict[str, Any]] = []

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
        import jax
        rec: Dict[str, Any] = {"name": name, **attrs}
        with jax.profiler.TraceAnnotation(name):
            rec["t0"] = time.perf_counter()
            try:
                yield rec
            finally:
                rec["t1"] = time.perf_counter()
                self.spans.append(rec)

    def of(self, name: str) -> List[Dict[str, Any]]:
        return [s for s in self.spans if s["name"] == name]


class CompileCounter:
    """Counts the programs JAX builds through ``jax.monitoring``: each is
    compiled by the backend or loaded from the persistent cache."""

    BUILD = "/jax/core/compile/backend_compile_duration"   # either way
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.programs = 0
        self.program_s = 0.0
        self.cache_hits = 0

    def install(self) -> None:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event == self.BUILD:
            self.programs += 1
            self.program_s += secs

    def _event(self, event: str, **_: Any) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"programs": self.programs, "program_s": self.program_s,
                "cache_loads": self.cache_hits,
                "compiles": self.programs - self.cache_hits}


def quantile(values: List[float], q: float) -> float:
    """The ``q`` quantile of all values (linear interpolation)."""
    return float(np.quantile(np.asarray(values, np.float64), q))
