"""All output tokens delivered to clients in the window over its length."""


def read(record):
    w = record["window"]
    return w["tokens_in_window"] / w["window_s"] or None
