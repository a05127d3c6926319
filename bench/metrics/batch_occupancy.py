"""batch_occupancy: active slots over the engine's slots, mean over the
window's decode steps, in percent."""


def read(record, trace):
    steps = record.get("steps")
    if not steps:
        return None
    return 100.0 * sum(len(s["lengths"]) / s["slots"] for s in steps) \
        / len(steps)
