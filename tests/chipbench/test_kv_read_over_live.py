"""``kv_read_over_live``: the reader against made ``step`` records."""

from chipbench import manifest as mf


def _record(steps):
    return {"kind": "serve", "t0": 0.0, "t1": 1.0, "config": {"name": "x"},
            "ledger": {"steps": steps, "capacity": 8}}


def _step(t0, **fields):
    return {"site": "decode.x", "kind": "step", "t0": t0, "dur_s": 0.01,
            "phases": {}, "active": 2, **fields}


def test_reads_rows_fetched_over_rows_live():
    steps = [_step(0.2, kv_live_rows=10, kv_read_rows=16, kv_rows=64),
             _step(0.4, kv_live_rows=30, kv_read_rows=32, kv_rows=64),
             # outside the window: not counted
             _step(1.5, kv_live_rows=1, kv_read_rows=64, kv_rows=64)]
    assert mf.reader("kv_read_over_live")(_record(steps)) == 48 / 40


def test_reads_nothing_from_a_program_without_the_field():
    """The parent commit's step records: no ``kv_read_rows``."""
    steps = [_step(0.2, kv_live_rows=10, kv_rows=64)]
    assert mf.reader("kv_read_over_live")(_record(steps)) is None
    assert mf.reader("kv_read_over_live")(_record([])) is None


def test_the_manifest_lists_it_for_the_serving_cells():
    man = mf.load_manifest()
    (entry,) = [m for m in man["per_layer"] if m["name"] == "kv_read_over_live"]
    assert entry["moves"] == "serve_tokens_per_s" and entry["unit"] == "ratio"
    serving = [m for m in man["end_to_end"]
               if m["name"] == "serve_tokens_per_s"][0]["workloads"]
    assert entry["workloads"] == serving
