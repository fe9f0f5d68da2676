"""``python -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one process, one cell, one plain window, one last line.

Builds the cell's runner (named by its traffic file), lets it set up and
warm up, measures one window of ``--seconds``, reads the peak memory, frees
the program's state, holds what the window produced against the float32
reference, and prints the contract's JSON object as the last line of
standard output. Fails at once, with no result line, where JAX finds no
TPU or fewer chips than the cell asks for. ``--rehearse`` (never passed by
the driver) runs the files' tiny ``rehearse`` presets on the CPU and names
the CPU in ``device``: a rehearsal's numbers are not device numbers.
"""

import time

_T_START = time.perf_counter()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402


def _parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None, metavar="NAME",
                    help="put this control of the configuration (a lower "
                         "precision, a planted fault) in the program's "
                         "place: the run has to end `correct: false` "
                         "(never passed by the driver)")
    return ap.parse_args(argv)


def cache_root(root: str) -> str:
    """The fixed directory, inside the checkout, of everything cached."""
    return os.path.join(root, ".chipbench_cache")


def setup_jax(root: str, rehearse: bool, chips: int):
    """Checks the device and points the persistent compile cache at its
    fixed place. Returns jax's device list."""
    import jax

    if not rehearse:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(cache_root(root), "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise SystemExit(f"chipbench: no TPU (jax found "
                             f"{devs[0].platform}); nothing measured")
        if len(devs) < chips:
            raise SystemExit(f"chipbench: the cell asks for {chips} chips, "
                             f"jax found {len(devs)}")
    return devs


def finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def judge(numbers: list) -> bool:
    return bool(numbers) and all(
        finite(n["value"]) and n["value"] <= n["limit"] for n in numbers)


def main(argv=None) -> int:
    args = _parse(argv)
    from . import manifest as mf
    from .harness import CompileLog, Context, memory_peak_bytes
    from .peaks import peaks_for

    man = mf.load_manifest()
    cell = mf.cell(man, args.workload)
    config = mf.load_config(mf.config_file(man, cell["config"]),
                            args.rehearse)
    from .loadgen import with_rehearsal
    traffic = with_rehearsal(
        mf.load_json(mf.traffic_file(cell["traffic"])), args.rehearse)
    devs = setup_jax(mf.ROOT, args.rehearse, int(cell["chips"]))
    t_devices = time.perf_counter()
    kind = devs[0].device_kind
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(cache_root(mf.ROOT), "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(config=config, traffic=traffic, seed=args.seed,
                  rehearse=args.rehearse,
                  cache_dir=cache_root(mf.ROOT), trace_dir=trace_dir,
                  t_start=_T_START, compiles=CompileLog(),
                  peaks=None if args.rehearse else peaks_for(kind))
    ctx.marks.append(["devices", t_devices - _T_START])
    runner = importlib.import_module("chipbench.runners."
                                     + traffic["runner"])
    state = runner.build(ctx)
    record = runner.measure(state, args.seconds)
    record["setup_s"] = record["t0"] - _T_START
    mem_peak = memory_peak_bytes()
    runner.release(state)
    del state
    record.update(model=ctx.model, peaks=ctx.peaks, config=config,
                  flops=ctx.family("flops"))
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    line = {}
    if args.trace:
        from . import xplane

        record["trace"] = xplane.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if record["trace"]:
            device["busy_s"] = record["trace"]["busy_s"]
            device["window_s"] = record["trace"]["window_s"]
            line["breakdown"] = {
                "device_ops": record["trace"]["device_ops"],
                "idle_gaps": record["trace"]["idle_gaps"]}
    t_check = time.perf_counter()
    numbers = runner.check(ctx, record, control=args.control)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in mf.metrics_of(man, args.workload, group):
        value = mf.reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": judge(numbers),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"]),
           "metrics": metrics, "device": device, **line,
           "seed": args.seed, "workload": args.workload}
    out["compile_log"] = ctx.compiles.snapshot()
    out["setup_marks"] = ctx.marks
    out["check_s"] = time.perf_counter() - t_check
    for key in ("notes", "checked_tokens"):
        if key in record:
            out[key] = record[key]
    if args.control:
        out["control"] = args.control
    out["compared"] = numbers
    sys.stdout.flush()
    for n in numbers:
        print(f"compared {n['name']} = {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
