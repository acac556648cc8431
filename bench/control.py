"""Readings that set a cell's correctness limit, many seeds in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed it builds the cell afresh (weights and prompts from that
seed), serves the mix for a short window at the cell's own load, and reads
on the same sample two widest gaps below the float32 reference's best logit:
that of the served tokens (the program: the limit's lower reading) and that
of the control, the reference computed in float8 (the upper reading). Each
gap is judged against the cell's limit by the harness's own rule
(``check.judge``), the control's in the program's place: ``correct`` for the
program, ``control_correct`` for the control, which has to be false. The
decode buckets are warmed once, for the first seed. One JSON line per seed on
standard output. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import check, spec  # noqa: E402
from harness.main import (LEAD_IN_MAX_S, Cluster, compare, enable_compile_cache,  # noqa: E402
                          served)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(spec.CHECKOUT / "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    enable_compile_cache()
    limits = cell.limits
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        sut = Cluster(cell, seed)
        if i == 0:
            sut.warm_up()
        drv = sut.load(seed)
        drv.lead_in(LEAD_IN_MAX_S)
        w0, _ = drv.run(args.seconds)
        drv.until_finished(w0, 60.0)
        sampled = served(check.sample(drv.records, w0, seed,
                                      int(limits["sample"]["tokens"]),
                                      int(limits["sample"]["max_requests"])))
        weights, shape = sut.weights, sut.shape
        sut.recorder.close()
        del drv, sut
        gc.collect()
        gaps = compare(cell, weights, shape, sampled, fp8=True)
        limit = float(limits["max_logit_gap"]["limit"])
        print(json.dumps({"workload": cell.name, "seed": seed, **gaps, "limit": limit,
                          "correct": check.judge(gaps["served"], limit, 0),
                          "control_correct": check.judge(gaps["control"], limit, 0),
                          "requests": len(sampled),
                          "tokens": sum(len(s["served"]) for s in sampled),
                          "seconds": time.monotonic() - t0}), flush=True)
        del weights
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
