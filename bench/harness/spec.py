"""What one cell is: its entry in ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, metric, cell or
model family lives in a file of its own, found by name:

* ``configs/<config>.json`` — the model's published ``config.json`` keys as
  run, plus ``serving`` (how the program is built and sized, and its
  ``family``);
* ``traffic/<traffic>.json`` — the parameters the one traffic generator reads;
* ``limits/<workload>.json`` — the limits of the numbers ``correct`` compares;
* ``metrics/<metric>.py`` — the reader of one metric (``metrics/<stem>.py``
  for a name ``<stem>.<part>`` with no file of its own);
* ``reference/<family>.py`` — everything the benchmark knows of one model
  family, found by the configuration's ``serving.family``:

  - ``shape(config) -> dict``: the sizes every other function reads, under
    one set of names, with ``family`` among them;
  - ``program_config(config, name)``: the program's ``ModelConfig``;
  - ``weight_shapes(shape) -> {path: (shape, init)}``: the weight tree that
    ``harness/weights.py`` makes, in the program's parameter layout;
  - ``prefill_flops(shape, offset, chunk)``, ``decode_flops(shape, cached)``:
    the model FLOPs of a prefill chunk and of one decode token;
  - ``attention_calls(shape) -> [(kv_heads, group, head_dim, window), ...]``:
    one entry per paged-attention call in a decode step (window 0: full);
  - ``logits(weights, shape, tokens, first, fp8=False)``: the float32
    reference, and with ``fp8`` the control.

  A configuration of a new family enters by adding files only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # configs/<config>.json
    traffic_name: str
    traffic: Dict[str, Any]         # traffic/<traffic>.json
    limits: Dict[str, Any]          # limits/<workload>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    run_seconds: int


def _read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, benchmark: Path = CHECKOUT / "BENCHMARK.json") -> Cell:
    """The cell named ``workload``; raises KeyError for an unknown name."""
    bench = _read_json(benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {benchmark}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"],
        config=_read_json(CHECKOUT / configs[w["config"]]["file"]),
        traffic_name=w["traffic"],
        traffic=_read_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(BENCH_DIR / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        run_seconds=int(bench["run_seconds"]))


def load_module(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module. A metric named ``<stem>.<part>``
    with no file of its own reads with ``<stem>.py``: one quantity filed under
    a second end-to-end metric that it moves, with no second reader."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH_DIR / kind / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if spec is None or spec.loader is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY = ("shape", "program_config", "weight_shapes", "prefill_flops", "decode_flops",
          "attention_calls", "logits")


def family(name: str) -> ModuleType:
    """``reference/<name>.py``; raises AttributeError where it lacks a
    function of the family interface (``FAMILY``)."""
    mod = load_module("reference", name)
    missing = [f for f in FAMILY if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"reference/{name}.py lacks {missing}")
    return mod


def model_shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes of a ``configs/<config>.json``, as its family reads them."""
    return family(config["serving"]["family"]).shape(config)
