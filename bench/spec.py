"""Finds a cell's files by the names in ``BENCHMARK.json``.

- ``BENCHMARK.json`` at the checkout root: the cell's configuration,
  traffic and metrics;
- the configuration's ``file``: published sizes, cuts, engine sizes;
- ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
- ``bench/cells/<cell>.json``: the cell's offered rate, run phases and
  correctness limits;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric
  (see ``reader``);
- ``bench/reference/<reference>.py``: the configuration's reference.

A later cell, configuration, traffic mix or metric is added as files.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
PEAKS = BENCH / "peaks.json"


@dataclasses.dataclass
class Cell:
    """Everything one cell's run reads from files."""

    name: str
    workload: dict        # the BENCHMARK.json entry
    config: dict          # the configuration file
    traffic: dict         # the traffic file
    params: dict          # the cell file
    end_to_end: list      # BENCHMARK.json end-to-end metrics of this cell
    per_layer: list       # BENCHMARK.json per-layer metrics of this cell


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Read the cell ``name`` and the files it names."""
    bench = _load(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, workload=w, config=_load(root / conf["file"]),
        traffic=_load(BENCH / "traffic" / f"{w['traffic']}.json"),
        params=_load(BENCH / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_module(path: Path):
    """Import a file of the benchmark as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The reader of a per-layer metric.

    ``bench/metrics/<metric>.py``, or, where that file does not exist,
    the reader of the quantity the metric splits:
    ``bench/metrics/prefill_share.py`` reads ``prefill_share.lat`` and
    ``prefill_share.tput`` alike. A reader's ``read(ctx)`` returns a
    number, or None where it found nothing to read.
    """
    if str(BENCH / "metrics") not in sys.path:
        sys.path.insert(0, str(BENCH / "metrics"))
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    return load_module(path).read


def reference(config: dict):
    """The reference module a configuration file names."""
    return load_module(BENCH / "reference" / f"{config['reference']}.py")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    table = _load(PEAKS)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file.

    The file's ``program`` section holds ``ModelConfig`` fields, with
    ``probe`` as ``ProbeConfig`` fields.
    """
    from repro.config import ModelConfig, ProbeConfig
    fields = dict(config["program"])
    probe = ProbeConfig(**fields.pop("probe", {}))
    return ModelConfig(name=config["name"], source=config["source"],
                       probe=probe, **fields)
