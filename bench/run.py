#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload qwen3-1.7b.batch-chat --seed 7 \\
        --seconds 30 --trace 0

One process, touching JAX once: the persistent compile cache is set, the
device is printed (anything but a TPU with the chips the cell asks for
is refused), the cell's driver builds the LIDC overlay and warms it up
(set-up), measures for ``--seconds`` (``--trace 1``: a shorter window
under the profiler instead), the compared numbers are worked out against
the float32 reference once the window is closed and the program's state
freed, and the last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``, each compared
number beside its limit (also the last lines of standard error).

The cell, its configuration, traffic mix, driver and metric readers are
found by name: ``BENCHMARK.json`` → ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json`` → ``bench/drivers/<driver>.py``,
``bench/metrics/<metric>.py``, ``bench/limits/<cell>.json``.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from typing import Any, Dict, Optional, Sequence, Tuple  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(_HERE), os.path.join(os.path.dirname(_HERE),
                                                     "src")]

from bench import trace as trace_mod  # noqa: E402
from bench.common import (BENCH_DIR, BenchError, CompileCounter,  # noqa: E402
                          SpanLog, cell_metrics, cell_of, config_file,
                          load_benchmark, load_module, traffic_file)

SPAN_NAMES = ("job", "executor", "engine_admit", "engine_step", "train_job")


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Tracer:
    """The profiled window of a ``--trace 1`` run: started with the
    window, stopped at the first engine step (or job end) past its
    length, so the trace covers whole steps."""

    def __init__(self, directory: Optional[str], seconds: float):
        self.dir = directory
        self.seconds = seconds
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self._ann = None

    def start(self) -> None:
        if self.dir is None:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        self._ann.__enter__()
        self.t0 = time.perf_counter()

    def maybe_stop(self, force: bool = False) -> None:
        if self._ann is None or self.t1 is not None:
            return
        now = time.perf_counter()
        if force or now - self.t0 >= self.seconds:
            import jax
            self._ann.__exit__(None, None, None)
            self.t1 = now
            jax.profiler.stop_trace()


class Context:
    """What a driver gets: the cell's files, the devices, the host spans,
    and the marks of set-up and window."""

    def __init__(self, args, cell, config, traffic, devices, peaks,
                 compiles: CompileCounter):
        self.args, self.seed = args, args.seed
        self.cell, self.config, self.traffic = cell, config, traffic
        self.devices, self.peaks, self.compiles = devices, peaks, compiles
        self.spans = SpanLog()
        self.log: Dict[str, Any] = {}
        self.setup_parts: Dict[str, float] = {}
        out = os.path.join(BENCH_DIR, "out", "trace-" + cell["name"])
        if args.trace:
            self.window_seconds = min(float(args.seconds),
                                      float(traffic.get("trace_seconds",
                                                        args.seconds)))
            self.tracer = Tracer(out, self.window_seconds)
        else:
            self.window_seconds = float(args.seconds)
            self.tracer = Tracer(None, 0.0)
        self.window_t0: Optional[float] = None
        self.compiles_at_window: Dict[str, float] = {}

    @contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.setup_parts[name] = (self.setup_parts.get(name, 0.0)
                                      + time.perf_counter() - t)

    def note(self, msg: str) -> None:
        say(msg)

    def start_window(self) -> float:
        self.compiles_at_window = self.compiles.snapshot()
        self.tracer.start()
        self.window_t0 = time.perf_counter()
        return self.window_t0

    def end_window(self, planned_end: float) -> float:
        """The window's end: ``planned_end``, or where the trace stopped."""
        self.tracer.maybe_stop(force=True)
        return self.tracer.t1 if self.tracer.t1 is not None else planned_end


def device_report(devices, chips: int) -> Dict[str, Any]:
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    for d, s in zip(devices, stats):
        say(f"memory {d}: peak_bytes_in_use={s.get('peak_bytes_in_use')} "
            f"peak_bytes_reserved={s.get('peak_bytes_reserved')} "
            f"bytes_limit={s.get('bytes_limit')}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips,
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def parse(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def run_cell(args: argparse.Namespace, *, require_tpu: bool = True,
             benchmark: Optional[Dict[str, Any]] = None,
             compiles: Optional[CompileCounter] = None,
             control=False) -> Tuple[int, Optional[Dict[str, Any]]]:
    """One run of one cell: ``(exit code, result)``.  With ``control`` (or
    the name of a planted fault the driver knows) the result also holds
    the control's reading of each compared number (``control_checks``),
    taken on the same requests."""
    try:
        bench = benchmark or load_benchmark()
        cell = cell_of(bench, args.workload)
        config = config_file(bench, cell["config"])
        traffic = traffic_file(cell["traffic"])
        driver = load_module("drivers", traffic["driver"])
        readers = {m["name"]: (m, load_module("metrics", m["name"]))
                   for m in cell_metrics(bench, cell["name"], bool(args.trace))}
        from repro.launch.compile_cache import configure_compile_cache
    except (BenchError, OSError, ImportError, KeyError) as e:
        print(f"[bench] cannot run: {e!r}", file=sys.stderr)
        return 2, None

    say(f"compile cache: {configure_compile_cache()}")
    import jax
    from bench.peaks import peaks_for
    from repro.kernels.ops import get_impl

    devices = jax.devices()
    dev = devices[0]
    say(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} kernels={get_impl()}")
    if require_tpu and dev.platform != "tpu":
        print(f"[bench] no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1, None
    if len(devices) < int(cell["chips"]):
        print(f"[bench] {cell['name']} needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1, None
    try:
        peaks = peaks_for(dev.device_kind) if require_tpu else None
    except KeyError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1, None

    if compiles is None:
        compiles = CompileCounter()
        compiles.install()
    ctx = Context(args, cell, config, traffic, devices, peaks, compiles)
    out = driver.run(ctx)
    record, verify = out["record"], out["verify"]
    chips = int(cell["chips"])
    record["setup_s"] = ctx.window_t0 - PROCESS_START
    report_run(ctx, record)
    device = device_report(devices, chips)

    tr = None
    if args.trace:
        raw = trace_mod.read(trace_mod.latest_xplane(ctx.tracer.dir))
        tr = trace_mod.reduce(raw, SPAN_NAMES, devices=list(range(chips)))
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        say(f"trace: window_s={tr['window_s']} busy_s={tr['busy_s']} "
            f"kernel_s={tr['kernel_s']} calls={tr['kernel_calls']}")

    metrics = {}
    for name, (spec, reader) in readers.items():
        value = reader.read(record, tr)
        if value is not None:
            metrics[name] = {"value": value, "unit": spec["unit"]}

    t = time.perf_counter()
    checks = verify()
    say(f"reference comparison took {time.perf_counter() - t} s")
    correct = all(c["value"] <= c["limit"] for c in checks)
    result: Dict[str, Any] = {
        "correct": correct, "attempted": record["jobs_attempted"],
        "failed": record["jobs_failed"], "metrics": metrics, "device": device}
    if tr is not None:
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    if control:
        result["control_checks"] = {c["name"]: c["value"]
                                    for c in verify(control=control)}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return 0, result


def main(argv: Optional[Sequence[str]] = None) -> int:
    code, result = run_cell(parse(argv))
    if result is None:
        return code
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return code


def report_run(ctx: Context, record: Dict[str, Any]) -> None:
    """The run's counts, on lines before the result."""
    after = ctx.compiles.snapshot()
    before = ctx.compiles_at_window
    parts = dict(ctx.setup_parts)
    parts["compile_or_cache_load"] = before["program_s"]
    say(f"setup_s={record['setup_s']} parts={parts}")
    say(f"jobs attempted={record['jobs_attempted']} "
        f"failed={record['jobs_failed']} requests attempted="
        f"{record.get('requests_attempted')} completed="
        f"{record.get('requests_completed')}")
    say(f"programs built inside the window: "
        f"{after['programs'] - before['programs']} (compiled "
        f"{after['compiles'] - before['compiles']}, loaded from the cache "
        f"{after['cache_loads'] - before['cache_loads']}); in set-up: "
        f"compiled {before['compiles']}, loaded {before['cache_loads']}")
    if "jobs_per_cluster" in record:
        say(f"jobs per cluster: {record['jobs_per_cluster']}")
    gaps = record.get("client_gap_s") or [0.0]
    say(f"client loop: closed; gap from one round's result to the next "
        f"round max={max(gaps)} s mean={sum(gaps) / len(gaps)} s")


if __name__ == "__main__":
    sys.exit(main())
