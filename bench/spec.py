"""Finds a cell's pieces by name.

``BENCHMARK.json`` at the root of the checkout names each cell's
configuration and traffic mix; the configuration is
``bench/configs/<config>.json`` (with its plain reference
``bench/configs/<config>.py`` beside it), the traffic mix
``bench/traffic/<traffic>.json``, and each per-layer metric a reader
``bench/layer_metrics/<metric>.py``. Adding a cell adds files and entries;
nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(RuntimeError):
    """A name that BENCHMARK.json or a file under bench/ does not resolve."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    end_to_end: list        # the metric entries this cell reports
    per_layer: list

    @property
    def kind(self) -> str:
        return self.config["kind"]


def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"{path.relative_to(ROOT)} is missing") from None


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = benchmark(root)
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{[w['name'] for w in spec['workloads']]}")
    w = found[0]
    config = load_json(BENCH / "configs" / f"{w['config']}.json")
    config["name"] = w["config"]
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    traffic["name"] = w["traffic"]
    if traffic["kind"] != config["kind"]:
        raise SpecError(f"{name}: traffic {w['traffic']!r} is for "
                        f"{traffic['kind']} engines, the configuration "
                        f"{w['config']!r} serves {config['kind']}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def load_module(path: pathlib.Path, name: str):
    """Import a file under bench/ by path (its name may hold '-' or '.')."""
    if not path.is_file():
        raise SpecError(f"{path.relative_to(ROOT)} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config: dict):
    """The configuration's plain reference, ``bench/configs/<name>.py``."""
    return load_module(BENCH / "configs" / f"{config['name']}.py",
                       f"bench_reference_{config['name']}")


def layer_reader(metric: str):
    """The reader of one per-layer metric:
    ``bench/layer_metrics/<name>.py``."""
    return load_module(BENCH / "layer_metrics" / f"{metric}.py",
                       f"bench_layer_{metric}")


def kernel_counts(kernel: str):
    """Operations and bytes of one Pallas kernel:
    ``bench/kernels/<name>.py``."""
    return load_module(BENCH / "kernels" / f"{kernel}.py",
                       f"bench_kernel_{kernel}")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json; have {sorted(table['devices'])}")
    return table["devices"][device_kind]
