"""``BENCHMARK.json`` and the files each of its names resolves to.

A cell (``workloads`` entry) resolves by name alone: its configuration to
the ``file`` its ``configs`` entry gives, its traffic mix to
``traffic/<traffic>.json``, its correctness limits to
``limits/<workload>.json`` and each per-layer metric to
``metrics/<metric>.py``. Inside those files, a configuration's model
``family`` resolves to ``families/<family>.py`` (the program's settings
and the work counts) and ``reference/<family>.py`` (the plain model), and
a mix's ``reference`` to ``reference/<reference>.py`` (the plain
semantics of its front door). Adding a configuration, mix or metric is
adding files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from typing import Callable, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def metric_reader(name: str) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


_MODULE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def module(package: str, name: str):
    """``<package>/<name>.py`` beside this package: a model family, or a
    plain reference."""
    if package not in ("families", "reference") or not _MODULE.match(name):
        raise KeyError(f"no module {package}/{name}.py")
    return importlib.import_module(f"{package}.{name}")


def applies(metric: dict, workload: str, reported: List[str]) -> bool:
    """Whether ``metric`` is reported in ``workload``: its ``workloads``
    list when it has one; otherwise an end-to-end metric holds everywhere
    and a per-layer one wherever the cell reports what it ``moves``."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in reported
    return True


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)


def resolve(workload: str, manifest: dict, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    entry = configs[w["config"]]
    config = _load_json(os.path.join(root, entry["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(BENCH_DIR, "limits",
                                     f"{workload}.json"))
    e2e = [m for m in manifest["end_to_end"] if applies(m, workload, [])]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in manifest["per_layer"]
                 if applies(m, workload, reported)]
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)
