"""The family seam: everything the benchmark knows of one model family is in
``reference/<family>.py``, so that a configuration of a new family enters by
adding files only.

* every family module provides the whole interface (``spec.FAMILY``);
* the weights a family's tree gives, from a fixed seed at a small size, are
  bit for bit those of the harness before the seam (frozen digests);
* a copy of ``bench/`` with one new family dropped in (a configuration,
  traffic, limits, a ``BENCHMARK.json`` entry and ``reference/<family>.py``,
  no other file touched) runs the whole chain in a process of its own:
  ``load_cell``, ``model_shape``, ``program_config``, ``W.make``, ``logits``,
  the ``step_mfu`` counts and the ``paged_attn_roofline`` least time.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from harness import spec
from harness import weights as W

FAMILIES = sorted(f[:-3] for f in os.listdir(spec.BENCH_DIR / "reference")
                  if f.endswith(".py") and not f.startswith("_"))


@pytest.mark.parametrize("name", FAMILIES)
def test_every_family_provides_the_interface(name):
    mod = spec.family(name)
    assert all(callable(getattr(mod, f)) for f in spec.FAMILY)


SMALL = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
         "tie_word_embeddings": True, "torch_dtype": "bfloat16",
         "serving": {"family": "dense", "qk_norm": True, "block_size": 32}}
SMALL_MOE = dict(SMALL, tie_word_embeddings=False, num_local_experts=4, num_experts_per_tok=2,
                 serving={"family": "moe", "block_size": 32})


def digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# Taken with the harness as it was before the family seam (spec.model_shape
# and weights.shapes of their own), seed 2**33 + 5.
@pytest.mark.parametrize("config, want", [
    (SMALL, "7ec1328bf5c466320b2aaa48a6caee82cd2e2a943d16f10610e53a467141727d"),
    (SMALL_MOE, "0383d666999c76d5aeacebce1d4cb1deff0c611c9dbece936c1d2fa007ef4d97"),
], ids=["dense", "moe"])
def test_weights_equal_the_frozen_digest(config, want):
    assert digest(W.make(spec.model_shape(config), 2**33 + 5)) == want


# A family the harness has never seen: a router over 8 published experts of
# which the chip holds 4, a zero-initialised routing bias, and sliding-window
# attention (48 tokens) in every other layer. Its reference is a toy for the
# seam (routing over the held experts' router columns, bias and window left
# out), not a model.
FAMILY_SRC = '''
"""A mixture of experts with a chip's share of the experts and windowed layers."""
import dataclasses

from reference import dense as D
from reference import moe as M


def shape(config):
    return dict(D.shape(config), experts=config["serving"]["experts_held"],
                router_experts=config["num_experts"], top_k=config["num_experts_per_tok"],
                window=config["sliding_window"],
                windowed=tuple(t == "sliding_attention" for t in config["layer_types"]))


def program_config(config, name):
    s = shape(config)
    return dataclasses.replace(D.program_config(config, name), family="moe", d_ff=0,
                               moe_d_ff=s["d_ff"], num_experts=s["experts"], top_k=s["top_k"])


def weight_shapes(s):
    L, d, E, R, f = s["layers"], s["d_model"], s["experts"], s["router_experts"], s["d_ff"]
    return D.weight_tree(s, {"moe_router": ((L, d, R), d), "moe_router_bias": ((L, R), "zeros"),
                             "moe_w_gate": ((L, E, d, f), d), "moe_w_up": ((L, E, d, f), d),
                             "moe_w_down": ((L, E, f, d), f)})


def layer_params(s):
    d = s["d_model"]
    return D.attn_params(s) + d * s["router_experts"] + s["top_k"] * 3 * d * s["d_ff"]


def pairs(s, offset, chunk, windowed):
    if not windowed:
        return D.attn_pairs(offset, chunk)
    return sum(min(offset + i + 1, s["window"]) for i in range(chunk))


def flops(s, offset, chunk):
    H, hd = s["heads"], s["head_dim"]
    return (sum(2 * layer_params(s) * chunk + 4 * H * hd * pairs(s, offset, chunk, w)
                for w in s["windowed"]) + 2 * s["vocab"] * s["d_model"])


def prefill_flops(s, offset, chunk):
    return flops(s, offset, chunk)


def decode_flops(s, cached):
    return flops(s, cached, 1)


def attention_calls(s):
    return [(s["kv_heads"], s["heads"] // s["kv_heads"], s["head_dim"], s["window"] if w else 0)
            for w in s["windowed"]]


def held(x, lp, s, fp8):
    return M.experts(x, dict(lp, moe_router=lp["moe_router"][:, :s["experts"]]), s, fp8)


def logits(weights, shape, tokens, first, fp8=False):
    return D.run(weights, shape, tokens, first, fp8, held)
'''

CONFIG = {
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "sliding_window": 48,
    "layer_types": ["sliding_attention", "full_attention"] * 2, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "serving": {"family": "toy_window_moe", "qk_norm": False, "block_size": 16,
                "experts_held": 4}}

CHAIN = '''
import json, sys
from types import SimpleNamespace as NS
sys.path[:0] = [sys.argv[1] + "/bench", sys.argv[2]]
import numpy as np
from harness import spec, trace as T
from harness import weights as W
from harness.window import Run

cell = spec.load_cell("toy-cell")
shape = spec.model_shape(cell.config)
fam = spec.family(shape["family"])
cfg = fam.program_config(cell.config, cell.config_name)
w = W.make(shape, 2**32 + 7)
tokens = np.random.default_rng(0).integers(0, shape["vocab"], 40).astype(np.int32)
logits = fam.logits(w, shape, tokens, 30)
attn = "(bf16[2,2,2,16]{3,2,1,0}, f32[2,2,2]{2,1,0}, f32[2,2,2]{2,1,0}) custom-call("
other = "(bf16[2,2,2,32]{3,2,1,0}, f32[2,2,2]{2,1,0}, f32[2,2,2]{2,1,0}) custom-call("
ops = [T.Op("%a", 0, 100_000, 0, "%a.1 = " + attn), T.Op("%a", 200_000, 100_000, 0, "%a.2 = " + attn),
       T.Op("%b", 400_000, 100_000, 0, "%b.1 = " + other)]
spans = {"prefill": [NS(end=1.0, attrs={"work": [(0, 64)]})],
         "decode": [NS(end=2.0, attrs={"lens": [100, 20]})], "transfer": []}
run = Run(0.0, 10.0, [], spans, [], shape, {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
          0.0, T.Reduced(0, 10**6, ops, 1, []))
print(json.dumps({
    "family": shape["family"], "program": [cfg.family, cfg.num_experts, cfg.moe_d_ff, cfg.top_k],
    "leaves": {k: list(v.shape) for k, v in w["layers"].items()},
    "bias_zero": bool((np.asarray(w["layers"]["moe_router_bias"]) == 0).all()),
    "logits": list(logits.shape), "finite": bool(np.isfinite(logits).all()),
    "decode_flops": fam.decode_flops(shape, 100), "prefill_flops": fam.prefill_flops(shape, 0, 64),
    "window_flops": spec.load_module("metrics", "step_mfu").window_flops(run),
    "step_mfu": spec.load_module("metrics", "step_mfu").read(run),
    "paged_attn_roofline": spec.load_module("metrics", "paged_attn_roofline").read(run)}))
'''


def add(root, rel, text):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), rel
    with open(path, "w") as f:
        f.write(text)


def test_new_family_enters_by_adding_files(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(spec.CHECKOUT / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy", "source": "-", "file": "bench/configs/toy.json",
                             "reduced": [], "why": "-"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy", "traffic": "toy",
                               "chips": 1, "why": "-"})
    add(root, "BENCHMARK.json", json.dumps(bench))
    add(root, "bench/reference/toy_window_moe.py", FAMILY_SRC)
    add(root, "bench/configs/toy.json", json.dumps(CONFIG))
    add(root, "bench/traffic/toy.json", json.dumps({"loop": "closed", "requests": 4}))
    add(root, "bench/limits/toy-cell.json", json.dumps({"max_logit_gap": {"limit": 0.1}}))
    add(root, "chain.py", CHAIN)
    src = str(spec.CHECKOUT / "src")
    out = subprocess.run([sys.executable, os.path.join(root, "chain.py"), root, src],
                         cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])

    assert got["family"] == "toy_window_moe"
    assert got["program"] == ["moe", 4, 32, 2]
    # the router keeps its published width (8) while the chip holds 4 experts
    assert got["leaves"]["moe_router"] == [4, 64, 8]
    assert got["leaves"]["moe_w_gate"] == [4, 4, 64, 32]
    assert got["leaves"]["moe_router_bias"] == [4, 8] and got["bias_zero"]
    assert got["logits"] == [10, 256] and got["finite"]
    # a layer: attention 3 x 64*64 = 12,288, router 64 x 8 = 512, 2 experts
    # of 3 x 64 x 32 = 12,288: 25,088 weights. A token after 100 cached
    # attends to 48 keys in a windowed layer, 101 in a full one; logits 2 x 256 x 64.
    assert got["decode_flops"] == 4 * 2 * 25_088 + 4 * 4 * 16 * (48 + 101) * 2 + 32_768
    # 64 tokens from 0: 2,080 causal pairs in a full layer, 1 + ... + 48 + 16 x 48 = 1,944
    # in a windowed one
    assert got["prefill_flops"] == 4 * 2 * 25_088 * 64 + 4 * 4 * 16 * (2_080 + 1_944) * 2 + 32_768
    # the decode step of two sequences (100 and 20 cached) and the prefill chunk
    dec20 = 4 * 2 * 25_088 + 4 * 4 * 16 * 21 * 4 + 32_768
    assert got["window_flops"] == got["prefill_flops"] + got["decode_flops"] + dec20
    assert got["step_mfu"] == pytest.approx(100 * got["window_flops"] / (10 * 1e12))
    # paged attention, bound by bytes at 1e9 B/s: per call K,V of the tokens
    # read, 2 x 2 heads x 16 x 2 B = 128 B a token, and 288 B a sequence of q,
    # out, m and l. Windowed calls read 48 + 20 tokens, full ones 100 + 20.
    # Two of each; the kernel of another head_dim is not this model's.
    least = 2 * (128 * 68 + 2 * 288) + 2 * (128 * 120 + 2 * 288)
    assert got["paged_attn_roofline"] == pytest.approx(100 * least * 1e-9 / 200e-6)
