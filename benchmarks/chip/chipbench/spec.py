"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's entry
names its file, which names its model family (``families/<family>.py``:
seeded weights and the hand-over to the system) and its plain reference
(``references/<reference>.py``). The mix is ``traffic/<traffic>.json``,
and every metric ``metrics/<name>.py``. Adding a cell, a configuration,
a mix or a metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parents[1]     # benchmarks/chip


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list

    def metrics(self, trace: bool) -> list:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    cfg["name"] = conf["name"]
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, w["chips"], cfg, mix,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def load_module(kind: str, name: str):
    """``families/<name>.py`` or ``references/<name>.py`` (identifiers),
    or ``metrics/<name>.py`` (a metric's name may hold dots)."""
    if kind != "metrics":
        return importlib.import_module(f"{kind}.{name}")
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chip_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
