"""Find a cell's files by the names in ``BENCHMARK.json``. A cell is an
entry of ``workloads``; its configuration, its traffic mix and each
per-layer metric are data files of their own, so a later PR adds cells by
adding files and entries and edits nothing that is here."""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file, as it is run
    traffic: dict         # the traffic mix's file
    bench: dict           # BENCHMARK.json
    root: pathlib.Path    # the checkout

    @property
    def home(self) -> pathlib.Path:
        return self.root / self.bench["paths"][0]

    def _applies(self, metric: dict, e2e_names) -> bool:
        """A per-layer metric is read in the cells it lists; one that
        lists none, in every cell that reports the metric it moves."""
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return metric["moves"] in e2e_names

    def end_to_end(self) -> list[dict]:
        """The end-to-end metrics this cell reports (``setup_s`` and
        those that list it, or list nothing)."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        """This cell's per-layer metrics, each with the reader and the
        arguments its own file names."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if self._applies(m, e2e):
                spec = _read(self.home / "metrics" / f"{m['name']}.json")
                out.append({**m, "reader": spec["reader"],
                            "args": spec.get("args", {})})
        return out


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root, workload: str) -> Cell:
    root = pathlib.Path(root)
    bench = _read(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    home = root / bench["paths"][0]
    return Cell(name=workload, chips=int(entry["chips"]),
                config=_read(root / config["file"]),
                traffic=_read(home / "traffic" / f"{entry['traffic']}.json"),
                bench=bench, root=root)


def _plugin(kind: str, name: str):
    pkg = __name__.rsplit(".", 2)[0]
    return importlib.import_module(f"{pkg}.{kind}.{name}")


def reader(name: str):
    """A per-layer metric's reader: ``read(ctx, **args)`` in a module of
    its own under ``readers/``, found by name."""
    return _plugin("readers", name).read


def builder(name: str):
    """A configuration's builder (``builders/<name>.py``): ``Run``."""
    return _plugin("builders", name).Run
