"""Executables asked of XLA during the window (must read 0)."""


def read(record):
    return record.get("compile_requests_in_window")
