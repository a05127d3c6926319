"""device_idle_share.train: 1 - (union of the device's operation
intervals) / (traced window), in percent, over whole train jobs (state
init and checkpoint included)."""


def read(record, trace):
    if trace is None or "train_tokens" not in record:
        return None
    return 100.0 * trace["idle_share"]
