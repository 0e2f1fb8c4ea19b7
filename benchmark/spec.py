"""BENCHMARK.json and the files it names, resolved for one cell.

A cell names a configuration and a traffic mix; both are found by name:
``configs/<config>.json`` (through the configuration's ``file``) and
``traffic/<traffic>.json``.  A configuration's file also gives the
matrix of the small copy its checks run on (``test_matrix``, merged into
``matrix``).  The metrics a cell reports are the entries of
``end_to_end`` and ``per_layer`` whose ``workloads`` list it (or that have
no such list), each read by ``metrics/<name>.py``."""

import dataclasses
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file, as run
    traffic: dict         # the mix's parameters
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _load(path):
    with open(path) as f:
        return json.load(f)


def _reports(metric, cell):
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def peaks(device_kind: str) -> dict:
    """The row of ``peaks.json`` for a device kind; an unknown kind is an
    error, never a default."""
    table = _load(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]
