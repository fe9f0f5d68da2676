"""Share of the held expert weights a decode step has to read: held
experts that at least one of the step's tokens chose (``experts_hit`` of
the ``step`` records, summed over the sparse layers) over held experts x
sparse layers x steps."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "experts_hit")
    slots = step_fields.held_slots(record["model"]) if rows else 0
    if not slots:
        return None
    return 100.0 * sum(h for h, in rows) / (slots * len(rows))
