"""train_tokens_per_s: tokens of every step of every train job completed
in the window, over the window (closed at the last completion)."""


def read(record, trace):
    if "train_tokens" not in record:
        return None
    return record["train_tokens"] / record["window_s"]
