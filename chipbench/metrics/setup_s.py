"""Process start to window start: loading, weights, warm-up, compiles."""


def read(record):
    return record["setup_s"]
