"""flash_attention_roofline.serve: the least time the window's prefill
flash_attention calls need (causal FLOPs and the q, k, v, o bytes of the
real prompt length; bench/flops.py and the chip's peaks) over the
kernel's device time in the trace, in percent."""

from bench import flops
from bench.peaks import min_seconds


def read(record, trace):
    if (trace is None or not trace["kernel_s"].get("flash_attention")
            or "admits" not in record):
        return None
    cfg = record["model"]
    layers = flops.dims(cfg)["L"]
    need = 0.0
    for a in record["admits"]:
        for n in a["prompts"]:
            w = flops.flash_attention_work(cfg, 1, n)
            need += layers * min_seconds(w["flops"], w["bytes"],
                                         record["peaks"])
    return 100.0 * need / trace["kernel_s"]["flash_attention"]
