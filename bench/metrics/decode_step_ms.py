"""decode_step_ms: wall time of the serve engine's ``step`` (one decode
step of every active slot, host round trip included), mean over the
window's steps."""


def read(record, trace):
    steps = record.get("steps")
    if not steps:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in steps) / len(steps)
