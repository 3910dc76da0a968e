"""What ``BENCHMARK.json`` declares, resolved to the files that hold it.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
``bench/configs/<config>.json`` and ``bench/mixes/<traffic>.json`` hold them.
A per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``.  Adding
any of these is a new file plus an entry in ``BENCHMARK.json``; nothing here
changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str, reported: set[str] | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _applies(m, name))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"] if _applies(m, name, reported))
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def reader(metric: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"per-layer metric {metric!r} has no reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table['devices'])}")
    return table["devices"][device_kind]


def field_params(mix: dict, field: str) -> dict:
    """The mix's parameters for one field (``"*"`` applies to every field)."""
    params = mix["params"]
    return params[field] if field in params else params["*"]
