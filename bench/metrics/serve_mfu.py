"""serve_mfu: model FLOPs of every prompt and generated token the window
processed (bench/flops.py), over window x chips x the chip's peak, in
percent."""

from bench import flops


def read(record, trace):
    if not record.get("steps") or not record.get("peaks"):
        return None
    cfg = record["model"]
    work = sum(flops.prefill_flops(cfg, n)
               for a in record["admits"] for n in a["prompts"])
    work += sum(flops.decode_flops(cfg, n)
                for s in record["steps"] for n in s["lengths"])
    peak = float(record["peaks"]["bf16_flops_per_s"])
    return 100.0 * work / (record["window_s"] * record["chips"] * peak)
