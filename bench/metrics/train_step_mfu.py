"""train_step_mfu: model FLOPs of one train step (forward and backward,
no recomputation; bench/flops.py) times the steps the trace holds, over
the device time of the ``jit_train_step`` program in the trace, over the
chip's peak, in percent.  It leaves out the job's stalls around the
steps (state init, checkpoint), which train_tokens_per_s includes."""

from bench import flops

PROGRAM = "jit_train_step"


def read(record, trace):
    if trace is None or "train_tokens" not in record:
        return None
    t = trace["module_s"].get(PROGRAM)
    if not t:
        return None
    work = flops.train_step_flops(record["model"], record["batch"],
                                  record["seq"])
    calls = trace["module_calls"][PROGRAM]
    peak = float(record["peaks"]["bf16_flops_per_s"])
    return 100.0 * work * calls / (t * peak)
