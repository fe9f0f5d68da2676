"""The window's length over the optimizer steps completed in it."""


def read(record):
    return 1e3 * record["window_s"] / record["steps"]
