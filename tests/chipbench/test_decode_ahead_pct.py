"""``decode_ahead_pct``: the reader against made ``step`` records."""

from chipbench import manifest as mf


def _record(steps):
    return {"kind": "serve", "t0": 0.0, "t1": 1.0, "config": {"name": "x"},
            "ledger": {"steps": steps, "capacity": 8}}


def _step(t0, **fields):
    return {"site": "decode.x", "kind": "step", "t0": t0, "dur_s": 0.01,
            "phases": {}, "active": 2, **fields}


def test_reads_the_share_of_steps_launched_ahead():
    steps = [_step(0.2, ahead=0), _step(0.4, ahead=1), _step(0.6, ahead=1),
             _step(0.8, ahead=1),
             # outside the window: not counted
             _step(1.5, ahead=0)]
    assert mf.reader("decode_ahead_pct")(_record(steps)) == 75.0


def test_reads_nothing_from_a_program_without_the_field():
    """The parent commit's step records: no ``ahead``."""
    assert mf.reader("decode_ahead_pct")(_record([_step(0.2)])) is None
    assert mf.reader("decode_ahead_pct")(_record([])) is None


def test_the_manifest_lists_it_for_the_serving_cells():
    man = mf.load_manifest()
    (entry,) = [m for m in man["per_layer"] if m["name"] == "decode_ahead_pct"]
    assert entry["moves"] == "serve_tokens_per_s" and entry["unit"] == "%"
    assert entry["layer"] == "decode scheduler"
    assert entry["source"] == "program_counter"
    serving = [m for m in man["end_to_end"]
               if m["name"] == "serve_tokens_per_s"][0]["workloads"]
    assert entry["workloads"] == serving
