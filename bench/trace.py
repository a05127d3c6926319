"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

On a TPU the device planes are ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation run on the core, nested (a ``while``
loop's event contains the events of its body).  Pallas kernels are
``custom-call`` operations named by the kernel's function, e.g.
``%flash_decode.4 = bf16[...] custom-call(...)``; the number after the
dot is the compiler's and changes between programs, so kernels are
matched by the name before it.  The host plane ``/host:CPU`` carries the
benchmark's ``TraceAnnotation`` spans on the same clock, which is how an
idle gap of the device is named by what the host was doing.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_KERNEL = re.compile(r"^%([A-Za-z_][A-Za-z0-9_]*?)(?:\.\d+)? = .*custom-call\(")

Interval = Tuple[float, float]


def latest_xplane(profile_dir: str) -> str:
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return max(files, key=os.path.getmtime)


def kernel_of(op_name: str) -> Optional[str]:
    """``flash_decode`` for ``%flash_decode.4 = ... custom-call(...)``."""
    m = _KERNEL.match(op_name)
    return m.group(1) if m else None


def short_name(op_name: str) -> str:
    """An operation's name and result shape, without layouts."""
    return op_name.split("{")[0].strip()[:120]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def self_times(ops: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Per operation name, the time no nested operation covers."""
    out: Dict[str, float] = {}
    stack: List[List] = []           # [end, name, self]

    def close(entry) -> None:
        out[entry[1]] = out.get(entry[1], 0.0) + entry[2]

    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    while stack:
        close(stack.pop())
    return out


def read(path: str) -> Dict[str, object]:
    """Device operations and whole programs per TPU core, and the host's
    spans, in ns on the trace's clock: ``{"devices": {id: [(start, end,
    name)]}, "modules": {id: [...]}, "spans": [(start, end, name)]}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Tuple[float, float, str]]] = {}
    modules: Dict[int, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    into = devices if line.name == "XLA Ops" else modules
                    into[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith("$"):     # python frames
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return {"devices": devices, "modules": modules, "spans": spans}


def reduce(raw: Dict[str, object], span_names: Sequence[str],
           devices: Optional[Sequence[int]] = None, top: int = 10
           ) -> Dict[str, object]:
    """Busy and idle time, kernel time and the breakdown of one traced
    window, the host span ``bench_window`` bounding it.

    Busy time is the union of the operations' intervals inside the
    window, averaged over ``devices`` (default: every TPU core traced).
    Kernel time is the sum of each kernel's events, over all devices;
    program time the same for each jitted program (``jit_train_step``).
    Each idle gap of the first device is named by the innermost of
    ``span_names`` open at its midpoint."""
    spans = [s for s in raw["spans"] if s[2] in span_names
             or s[2] == WINDOW_SPAN]
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = win[0][0], win[0][1]
    ids = sorted(devices if devices is not None else raw["devices"])
    busy, kernels, kernel_calls = [], {}, {}
    all_ops = []
    for i in ids:
        ops = [o for o in raw["devices"].get(i, []) if o[1] > w0 and o[0] < w1]
        busy.append(sum(b - a for a, b in
                        clip(union((a, b) for a, b, _ in ops), w0, w1)))
        for a, b, name in ops:
            k = kernel_of(name)
            if k:
                kernels[k] = kernels.get(k, 0.0) + (b - a)
                kernel_calls[k] = kernel_calls.get(k, 0) + 1
        all_ops += ops
    modules, module_calls = {}, {}
    for i in ids:
        for a, b, name in raw.get("modules", {}).get(i, []):
            if b > w0 and a < w1:
                prog = name.split("(")[0]
                modules[prog] = modules.get(prog, 0.0) + (b - a)
                module_calls[prog] = module_calls.get(prog, 0) + 1
    selfs = self_times([(a, b, short_name(n)) for a, b, n in all_ops])
    device_ops = sorted(selfs.items(), key=lambda kv: -kv[1])[:top]

    first = clip(union((a, b) for a, b, _ in
                       (raw["devices"].get(ids[0], []) if ids else [])),
                 w0, w1)
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        open_ = [s for s in spans if s[0] <= mid <= s[1] and s[2] != WINDOW_SPAN]
        name = min(open_, key=lambda s: s[1] - s[0])[2] if open_ else "none"
        named.append([name, (b - a) / 1e9])
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / max(len(busy), 1) / 1e9
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "kernel_s": {k: v / 1e9 for k, v in kernels.items()},
            "kernel_calls": kernel_calls,
            "module_s": {k: v / 1e9 for k, v in modules.items()},
            "module_calls": module_calls,
            "device_ops": [[n, v / 1e9] for n, v in device_ops],
            "idle_gaps": named}
