"""Finds a cell's files by the names in ``BENCHMARK.json``:

    configs/<config>.json     sizes, engine settings, source, reduced,
                              assumed, deployment, the names of its weights
                              module and reference, and the limit of the
                              correctness check
    traffic/<traffic>.json    the mix, read by harness/traffic.py
    metrics/<metric>.py       one reader per per-layer metric: read(w)
    weights/<name>.py         the weights module a configuration names:
                              root_key(seed), program_params(model, seed)
                              (the program's parameter tree, made on the
                              device in one jitted call), layer(key, model,
                              l, dtype) and top(key, model, dtype) (the
                              leaves of layer l and of the embedding and
                              head, for the reference to make again), and
                              matmul_weights_per_token(model) (the weights
                              of the matrix products one token passes
                              through; of experts, the active ones)
    references/<name>.py      the plain reference a configuration names; it
                              is given the configuration's weights module

A new cell, configuration, mix or per-layer metric is new files plus new
entries in BENCHMARK.json, whatever the configuration's parameter tree;
nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(path: Optional[str] = None) -> dict:
    with open(path or os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, workload: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    config = _json("configs", w["config"])
    name = config.get("weights")
    if not name:
        raise ValueError(f"configuration {w['config']!r} names no weights "
                         "module (\"weights\": <name> of weights/<name>.py)")
    if not os.path.isfile(_weights_path(name)):
        raise ValueError(f"configuration {w['config']!r} names the weights "
                         f"module {name!r}, and there is no "
                         f"{_weights_path(name)}")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=_json("traffic", w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def _load(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    """The ``read(window)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    safe = metric.replace(".", "_").replace("-", "_")
    return _load(path, f"bench_metric_{safe}").read


def _weights_path(name: str) -> str:
    return os.path.join(HERE, "weights", f"{name}.py")


def weights(name: str):
    """The module ``weights/<name>.py`` (see the list above)."""
    safe = name.replace(".", "_").replace("-", "_")
    return _load(_weights_path(name), f"bench_weights_{safe}")


def reference(name: str):
    return _load(os.path.join(HERE, "references", f"{name}.py"),
                 f"bench_reference_{name}")
