"""device_idle_share.serve: 1 - (union of the device's operation
intervals) / (traced window), averaged over the cell's chips, in
percent."""


def read(record, trace):
    if trace is None or "steps" not in record:
        return None
    return 100.0 * trace["idle_share"]
