"""What a cell is made of, found by name under ``bench/``.

``BENCHMARK.json`` at the checkout root pairs a configuration with a
traffic mix. Everything that belongs to one of them sits in a file of its
own, which this module finds by the name alone:

* ``bench/configs/<config>.json`` — the model, its sizes and the engine's
  serving shape (``max_batch``, ``max_seq``, ``page_size``);
* ``bench/traffic/<mix>.json`` — the loop kind and the length and arrival
  parameters that ``bench/traffic.py`` reads;
* ``bench/metrics/<metric>.py`` — one metric, a function ``compute(rec)``
  of the run's records (``<metric>`` up to its first ``.`` where the name
  is split by cells);
* ``bench/reference/<module>.py`` — the plain reference the configuration
  names;
* ``bench/peaks.json`` — the chip's published peaks, keyed by
  ``device_kind``.

A later cell, mix or metric is added as files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A name that no file answers to, or a file that breaks its shape."""


def _read_json(path: pathlib.Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no such file: {path}") from None


def benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _read_json(root / "BENCHMARK.json")


def workload(name: str, root: pathlib.Path = ROOT) -> Dict[str, Any]:
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")


def config(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, Any]:
    return _read_json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, Any]:
    mix = _read_json(bench_dir / "traffic" / f"{name}.json")
    if mix.get("loop") not in ("open", "closed"):
        raise SpecError(f"traffic {name!r}: loop must be 'open' or 'closed'")
    return mix


def peaks(device_kind: str, bench_dir: pathlib.Path = BENCH_DIR
          ) -> Dict[str, Any]:
    table = _read_json(bench_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} has no entry in "
                        f"{bench_dir / 'peaks.json'}")
    return table["devices"][device_kind]


def _load_module(path: pathlib.Path, mod_name: str):
    if not path.is_file():
        raise SpecError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_fn(name: str, bench_dir: pathlib.Path = BENCH_DIR
              ) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``compute(rec)`` of ``bench/metrics/<name>.py``. A name split by the
    cells that report it (``step_mfu.chat``, ``step_mfu.longdoc``: one
    quantity moving different end-to-end metrics) falls back to the file
    of its first part, ``step_mfu.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        path = bench_dir / "metrics" / f"{name.split('.')[0]}.py"
    mod = _load_module(path, "bench_metric_" + name.replace(".", "_"))
    return mod.compute


def reference_module(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    return _load_module(bench_dir / "reference" / f"{name}.py",
                        "bench_reference_" + name)


def metrics_for(bench: Dict[str, Any], cell: str, trace: bool
                ) -> List[Dict[str, Any]]:
    """The metrics a run of ``cell`` reports: the end-to-end ones with
    ``trace`` off, the per-layer ones with it on. A metric without a
    ``workloads`` key is reported in every cell."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
