"""Resolve a cell from ``BENCHMARK.json`` and the files named after it.

Nothing here lists cells, configurations, traffic mixes or metrics: a
cell's entry in ``BENCHMARK.json`` names its configuration and traffic,
and each of those, like each per-layer metric, is a file found by its
name alone:

    configs/<config>.json     sizes, domains, guarantee and limits
    traffic/<traffic>.json    the mix that ``loadgen`` reads
    metrics/<metric>.py       a reader with ``read(record)``

A metric split by cell (``device_idle_share.scan``, one per end-to-end
metric it moves) is read by the reader of its base name
(``metrics/device_idle_share.py``).
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ValueError(f"bad name {name!r}: letters, digits, _ . - only")
    return name


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    end_to_end: List[dict]        # the end-to-end metrics this cell reports
    per_layer: List[dict]         # the per-layer metrics this cell reports


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def cell(name: str, bench: dict) -> Cell:
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    return Cell(check_name(w["name"]), check_name(w["config"]),
                check_name(w["traffic"]), int(w["chips"]), e2e,
                [m for m in bench["per_layer"] if _applies(m, name, names)])


def config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{check_name(name)}.json")
                      .read_text())


def traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{check_name(name)}.json")
                      .read_text())


def metric_path(name: str) -> Path:
    return HERE / "metrics" / (check_name(name).split(".", 1)[0] + ".py")


def reader(name: str) -> Callable[[dict], object]:
    """The ``read`` function of ``metrics/<base name>.py``."""
    path = metric_path(name)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
