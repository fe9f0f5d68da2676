"""Of the rows the K/V cache holds (layers x slots x rows, every group),
the share that held a position some decode step of the window read:
``kv_live_rows`` over ``kv_rows`` of the ``step`` records."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "kv_live_rows", "kv_rows")
    if not rows or not sum(a for _, a in rows):
        return None
    return 100.0 * sum(l for l, _ in rows) / sum(a for _, a in rows)
