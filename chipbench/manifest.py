"""``BENCHMARK.json`` and the files it names. A cell names its
configuration and its traffic mix; the files are found by those names
under ``chipbench/configs`` and ``chipbench/traffic``, and each metric's
reader under ``chipbench/metrics``. No name is written into the code."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_file(manifest: dict, config: str, root: str = ROOT) -> str:
    for c in manifest["configs"]:
        if c["name"] == config:
            return os.path.join(root, c["file"])
    raise KeyError(f"no configuration {config!r} in BENCHMARK.json")


def traffic_file(traffic: str) -> str:
    return os.path.join(HERE, "traffic", traffic + ".json")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def overlay(base: dict, over: dict) -> dict:
    """``over`` laid on ``base``, dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if (isinstance(v, dict)
                                        and isinstance(out.get(k), dict)) \
            else v
    return out


def load_config(path: str, rehearse: bool) -> dict:
    cfg = load_json(path)
    over = cfg.pop("rehearse", {})
    return overlay(cfg, over) if rehearse else cfg


def metrics_of(manifest: dict, workload: str, group: str) -> list:
    """The metrics of ``group`` (``end_to_end`` | ``per_layer``) that this
    cell reports: those that list it, and those that list no cells."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def reader(metric: str):
    """``chipbench/metrics/<metric>.py``'s ``read(record)``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
