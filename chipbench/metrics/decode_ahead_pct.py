"""Share of the window's decode steps that were dispatched while the step
before them was still unfetched: field ``ahead`` (0/1) of the ``step``
records. 0 is a session that waits a host round trip per token; near 100
one whose device goes from step to step. Nothing on a program whose
records lack the field."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "ahead")
    if not rows:
        return None
    return 100.0 * sum(ahead for ahead, in rows) / len(rows)
