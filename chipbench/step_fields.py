"""Integers the decode step itself reports, read from the window's ``step``
records of the turn ledger (fields beside ``phases``; which program writes
which: docs/OBSERVABILITY.md "Phases and the turn ledger")."""

from __future__ import annotations

from typing import List, Optional

from . import ledger


def columns(record: dict, *names: str) -> Optional[List[tuple]]:
    """Per ``step`` record of the window the values of ``names``; None
    where the ledger cannot show the window or a record lacks a field (a
    program that does not report it)."""
    steps = ledger.turns(record, "step")
    if not steps or any(n not in r for r in steps for n in names):
        return None
    return [tuple(r[n] for n in names) for r in steps]


def held_slots(model: dict) -> int:
    """Held experts x sparse layers: the expert weights a step could hit."""
    n = int(model["num_hidden_layers"])
    sparse = sum(1 for m in model["mlp_layer_types"][:n] if m == "sparse")
    return int(model["num_experts"]) * sparse
