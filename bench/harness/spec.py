"""What one cell is: its entry in ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, metric or cell
lives in a file of its own, found by name:

* ``configs/<config>.json`` — the model's published ``config.json`` keys as
  run, plus ``serving`` (how the program is built and sized);
* ``traffic/<traffic>.json`` — the parameters the one traffic generator reads;
* ``limits/<workload>.json`` — the limits of the numbers ``correct`` compares;
* ``metrics/<metric>.py`` — the reader of one metric (``metrics/<stem>.py``
  for a name ``<stem>.<part>`` with no file of its own);
* ``reference/<family>.py`` — the float32 reference of one model family.
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


def model_shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes every consumer (weights, reference, FLOP counts) reads,
    under one set of names, from a ``configs/<config>.json``."""
    serving = config["serving"]
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    moe = serving["family"] == "moe"
    return {
        "family": serving["family"],
        "layers": config["num_hidden_layers"],
        "d_model": d,
        "heads": h,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or serving["head_dim"],
        "d_ff": config["intermediate_size"],
        "experts": config["num_local_experts"] if moe else 0,
        "top_k": config["num_experts_per_tok"] if moe else 0,
        "vocab": config["vocab_size"],
        "qk_norm": bool(serving.get("qk_norm", False)),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "tied": bool(config["tie_word_embeddings"]),
        "dtype": config["torch_dtype"],
        "block_size": int(serving["block_size"]),
    }


def program_config(config: Dict[str, Any], name: str):
    """The program's ``ModelConfig`` for a configuration file."""
    import jax.numpy as jnp
    from repro.models.common import ModelConfig

    s = model_shape(config)
    return ModelConfig(
        name=name, family=s["family"], num_layers=s["layers"],
        d_model=s["d_model"], num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=0 if s["family"] == "moe" else s["d_ff"],
        moe_d_ff=s["d_ff"] if s["family"] == "moe" else 0,
        num_experts=s["experts"], top_k=s["top_k"], qk_norm=s["qk_norm"],
        vocab_size=s["vocab"], rope_theta=s["rope_theta"], norm_eps=s["norm_eps"],
        tie_embeddings=s["tied"], dtype=getattr(jnp, s["dtype"]),
        block_size=s["block_size"])
