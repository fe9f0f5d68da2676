"""Rows the decode steps' attention READ (of the K/V cache, and each
slot's new row) over the rows that held a position they read:
``kv_read_rows`` over ``kv_live_rows`` of the window's ``step`` records.
1 is a step that reads its live rows and no others; whole blocks, or
whole planes where a group is read dense, read above it."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "kv_read_rows", "kv_live_rows")
    if not rows or not sum(live for _, live in rows):
        return None
    return sum(r for r, _ in rows) / sum(live for _, live in rows)
