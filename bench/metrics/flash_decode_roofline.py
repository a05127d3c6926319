"""flash_decode_roofline: the least time the window's flash_decode calls
need (each active row's valid K/V, its query and output; bench/flops.py
and the chip's peaks) over the kernel's device time in the trace, in
percent."""

from bench import flops
from bench.peaks import min_seconds


def read(record, trace):
    if trace is None or not trace["kernel_s"].get("flash_decode"):
        return None
    cfg = record["model"]
    layers = flops.dims(cfg)["L"]
    need = 0.0
    for s in record["steps"]:
        w = flops.flash_decode_work(cfg, s["lengths"])
        need += layers * min_seconds(w["flops"], w["bytes"], record["peaks"])
    return 100.0 * need / trace["kernel_s"]["flash_decode"]
