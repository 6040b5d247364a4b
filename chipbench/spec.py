"""Finds a cell's parts by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic file, and the code they name: the
problem generator and the entry the window drives (from the
configuration's ``generator`` and ``entry``), the traffic's driver
(``driver``) and the reader of each metric the cell reports.

Data lives under the checkout (``BENCHMARK.json`` and the files it names,
``chipbench/traffic/<traffic>.json``); code lives in
``chipbench/<kind>/<name>.py`` of the checkout, or beside this file:
``problems/``, ``entries/``, ``drivers/``, ``metrics/``. Adding a cell,
a traffic mix, a kind of traffic, an entry, a generator or a metric means
adding files, never editing one.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """A cell, file or name the benchmark cannot resolve."""


def _checked(name: str, what: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError("bad %s name %r" % (what, name))
    return name


def _load_json(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise SpecError("cannot read %s: %s" % (path, e)) from None


_LOADED: Dict[Path, Any] = {}


def module(root: Path, kind: str, name: str):
    """The code file ``<kind>/<name>.py``: the checkout's own where it
    has one, else the one beside this file; loaded once."""
    fname = "%s.py" % _checked(name, kind)
    paths = [Path(root) / "chipbench" / kind / fname, HERE / kind / fname]
    path = next((p.resolve() for p in paths if p.is_file()), None)
    if path is None:
        raise SpecError("no %s named %r (%s)" % (kind, name, paths[-1]))
    if path not in _LOADED:
        mod_spec = importlib.util.spec_from_file_location(
            "chipbench_%s_%s" % (kind, re.sub(r"[.-]", "_", name)), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


@dataclass
class Metric:
    name: str
    unit: str
    #: the module whose ``read(rec)`` gives the metric's value from the
    #: run record, or None where the run has nothing for it to read
    reader: Any


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    #: ``problems/<generator>.py``: ``build(config)`` gives the operator
    problem: Any
    #: ``entries/<entry>.py``: builds and reads what the window drives
    entry: Any
    #: ``drivers/<driver>.py``: ``prepare``, ``warm`` and ``window``
    driver: Any
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)

    def build_problem(self):
        """The configuration's operator, from its frozen generator."""
        return self.problem.build(self.config)


def _metrics(root: Path, entries, cell_name: str) -> List[Metric]:
    out = []
    for m in entries:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        out.append(Metric(m["name"], m["unit"],
                          module(root, "metrics", m["name"])))
    return out


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of the benchmark at checkout ``root``."""
    root = Path(root)
    bench = _load_json(root / "BENCHMARK.json")
    _checked(name, "workload")
    cells = [w for w in bench.get("workloads", []) if w.get("name") == name]
    if len(cells) != 1:
        raise SpecError("no workload named %r in %s"
                        % (name, root / "BENCHMARK.json"))
    w = cells[0]
    confs = [c for c in bench.get("configs", [])
             if c.get("name") == w["config"]]
    if len(confs) != 1:
        raise SpecError("workload %r names unknown config %r"
                        % (name, w["config"]))
    config = _load_json(root / confs[0]["file"])
    traffic = _load_json(root / "chipbench" / "traffic" / (
        "%s.json" % _checked(w["traffic"], "traffic")))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                problem=module(root, "problems", config["generator"]),
                entry=module(root, "entries", config["entry"]),
                driver=module(root, "drivers", traffic["driver"]),
                end_to_end=_metrics(root, bench.get("end_to_end", []), name),
                per_layer=_metrics(root, bench.get("per_layer", []), name))
