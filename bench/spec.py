"""What one run is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each of those, and each
metric, lives in files of its own that this module finds by name:

* configuration ``<c>``: ``bench/configs/<c>.json`` (the sizes as run,
  and under ``check`` the limits of the comparison, ``bench/check.py``),
  ``bench/configs/<c>.py`` (the model handed to repro, its weights, inputs
  and GEMM list, and ``test_sizes(cfg, traffic)``, which cuts a loaded
  configuration and mix in place to the sizes the CPU tests drive) and
  ``bench/configs/<c>_ref.py`` (the plain reference);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, parameters that
  ``bench.generator`` reads;
* metric ``<m>``: ``bench/metrics/<m>.py``, a ``read(run)`` that returns a
  number or None when the run holds nothing to read.

Adding a configuration, a mix or a metric is adding files and entries;
``layout_errors`` says what the entries must keep to.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: most cells a benchmark may have
MAX_CELLS = 24


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    model: ModuleType
    ref: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path = ROOT  # the checkout whose files the cell was found in

    def metrics(self, trace: bool) -> list[dict]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {', '.join(by_name)}")
    w = by_name[name]
    c = w["config"]
    configs = root / "bench" / "configs"
    return Cell(
        name=name,
        chips=w["chips"],
        config=json.loads((configs / f"{c}.json").read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        model=load_module(configs / f"{c}.py", f"bench_config_{c}"),
        ref=load_module(configs / f"{c}_ref.py", f"bench_ref_{c}"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root,
    )


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of metric ``name``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def layout_errors(bench: dict) -> list[str]:
    """What is wrong with the cells of ``bench``, a ``BENCHMARK.json``: names
    unique, at most ``MAX_CELLS`` cells, each pair of configuration and mix
    once, every cell's configuration listed and every configuration used,
    1 or 4 chips to a cell and at most half of the cells (at least one) on
    4, and a metric's ``workloads`` naming cells that exist."""
    cells, configs = bench["workloads"], bench["configs"]
    errors = []
    for what, entries in (("cell", cells), ("configuration", configs)):
        names = [e["name"] for e in entries]
        errors += [f"{what} {n!r} appears {names.count(n)} times"
                   for n in sorted(set(names)) if names.count(n) > 1]
    if len(cells) > MAX_CELLS:
        errors.append(f"{len(cells)} cells; at most {MAX_CELLS}")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    errors += [f"configuration {c!r} under mix {t!r} in {pairs.count((c, t))} cells"
               for c, t in sorted(set(pairs)) if pairs.count((c, t)) > 1]
    listed = {c["name"] for c in configs}
    used = {w["config"] for w in cells}
    errors += [f"cell {w['name']!r} names configuration {w['config']!r}, which is not listed"
               for w in cells if w["config"] not in listed]
    errors += [f"configuration {c!r} has no cell" for c in sorted(listed - used)]
    errors += [f"cell {w['name']!r} asks for {w['chips']} chips; 1 or 4"
               for w in cells if w["chips"] not in (1, 4)]
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 2):
        errors.append(f"{four} of {len(cells)} cells on 4 chips; at most {max(1, len(cells) // 2)}")
    known = {w["name"] for w in cells}
    for m in bench["end_to_end"] + bench["per_layer"]:
        errors += [f"metric {m['name']!r} lists unknown cell {c!r}"
                   for c in m.get("workloads", []) if c not in known]
    return errors
