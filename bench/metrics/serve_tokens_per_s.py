"""serve_tokens_per_s: tokens the serve engines emitted inside the
window (prefill's first token and every decode token), over the window's
wall time."""


def read(record, trace):
    if "steps" not in record:
        return None
    return record["tokens"] / record["window_s"]
