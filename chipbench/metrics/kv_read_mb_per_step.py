"""Megabytes of cache the mean decode step's attention READ, as they are
stored: ``kv_read_bytes`` of the window's ``step`` records (rows read x
the stored row's bytes, both tensors of a K/V pair, the one of a latent
group). None on a program whose steps do not report it."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "kv_read_bytes")
    if not rows:
        return None
    return sum(b for b, in rows) / len(rows) / 1e6
