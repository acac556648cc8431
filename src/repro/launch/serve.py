"""End-to-end serving driver (the paper is a serving system, so this is the
primary launcher).

Two modes:

* ``--engine real``  — real JAX compute through the PD cluster via the
  :class:`repro.serving.api.FlowKVClient` streaming facade, token-correct
  generation, real FlowKV page transfers. The smoke-sized config by
  default; ``--full`` builds the published config (random bf16 weights
  made from ``--seed``), the size to run on a TPU.
* ``--engine sim``   — cluster-scale: discrete-event simulation driving the
  same control plane with calibrated hardware costs (A100/L20/H20/TPUv5e).

Examples:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --engine real --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --engine real --full
    PYTHONPATH=src python -m repro.launch.serve --arch llama31-8b --engine sim \\
        --system flowkv --workload 10k --rps 1.0
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def run_real(args) -> dict:
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.models.api import get_model
    from repro.serving.api import FlowKVClient
    from repro.serving.request import SamplingParams

    cfg = (get_config if args.full else get_smoke_config)(args.arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    client = FlowKVClient(cfg, params, num_prefill=args.num_prefill,
                          num_decode=args.num_decode, num_blocks=args.blocks,
                          transfer_schedule=args.schedule,
                          role_flip=args.role_flip)
    rng = np.random.RandomState(args.seed)
    handles = [client.submit(rng.randint(0, cfg.vocab_size,
                                         size=rng.randint(8, 48)).tolist(),
                             SamplingParams(max_new_tokens=args.max_new_tokens))
               for _ in range(args.requests)]
    client.drain(max_cycles=500)
    stats = client.stats()
    stats["outputs"] = {h.request_id: h.request.output_tokens
                        for h in handles[:4]}
    stats["timing"] = {h.request_id: h.stats() for h in handles[:4]}
    return stats


def run_sim(args) -> dict:
    from repro.configs import get_config
    from repro.sim.cluster_sim import ClusterSim
    from repro.sim.hardware import get_hardware
    from repro.sim.workload import LONGBENCH, SIMULATED, generate

    cfg = get_config(args.arch)
    wl = {**SIMULATED, **LONGBENCH}[args.workload]
    sim = ClusterSim(cfg, args.system, num_prefill=args.num_prefill,
                     num_decode=args.num_decode,
                     hw_prefill=get_hardware(args.hw_prefill),
                     hw_decode=get_hardware(args.hw_decode),
                     same_host=args.same_host, tp=args.tp)
    return sim.run(generate(wl, rps=args.rps, seed=args.seed), t_max=100_000)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--engine", choices=("real", "sim"), default="real")
    ap.add_argument("--full", action="store_true",
                    help="real engine: the published config instead of the "
                         "smoke-sized one")
    ap.add_argument("--system", default="flowkv")
    ap.add_argument("--schedule", default="flowkv",
                    choices=("flowkv", "layerwise", "blockwise"))
    ap.add_argument("--workload", default="1k")
    ap.add_argument("--rps", type=float, default=1.0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--num-prefill", type=int, default=1)
    ap.add_argument("--num-decode", type=int, default=1)
    ap.add_argument("--blocks", type=int, default=256)
    ap.add_argument("--role-flip", action="store_true",
                    help="let the load-aware scheduler reassign P<->D roles "
                         "under imbalance (real engine)")
    ap.add_argument("--hw-prefill", default="a100")
    ap.add_argument("--hw-decode", default="a100")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--same-host", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    stats = run_real(args) if args.engine == "real" else run_sim(args)
    print(json.dumps(stats, indent=1, default=str))


if __name__ == "__main__":
    main()
