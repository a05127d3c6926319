"""flash_attention_roofline.train: the least time the traced train
steps' forward flash_attention calls need (causal FLOPs and the q, k, v,
o bytes of batch x seq; bench/flops.py and the chip's peaks) over the
kernel's device time in the trace, in percent.  The backward pass
recomputes through the jnp reference and is not this kernel's."""

from bench import flops
from bench.peaks import min_seconds


def read(record, trace):
    if (trace is None or "train_tokens" not in record
            or not trace["kernel_s"].get("flash_attention")):
        return None
    w = flops.flash_attention_work(record["model"], record["batch"],
                                   record["seq"])
    need = trace["kernel_calls"]["flash_attention"] * min_seconds(
        w["flops"], w["bytes"], record["peaks"])
    return 100.0 * need / trace["kernel_s"]["flash_attention"]
