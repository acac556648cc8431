"""The mixture-of-experts reference (``reference/moe.py``) against the
program's MoE model, on the CPU at a small size with the benchmark's seeded
weights: the program prefills each prompt and then decodes the two requests
together through its paged step (``decode_paged``, the step the serving
engine runs), greedily; the float32 reference, teacher-forced on the same
tokens, gives the logits of the same positions.

The program runs here in float32 (``torch_dtype`` float32: weights, pool and
activations), so that the comparison tests what is computed, not how it is
rounded. In bfloat16, as served, top-k routing is discontinuous: where a
token's second and third router probabilities lie within bf16 rounding of
each other (0.001 apart on one seed of three tried), the program can choose
another expert than the reference, and the two then differ by a fault's
size. That is the nature of routing under rounding, not a fault.

Tolerance: float32 on both sides, differing only in the order of sums (the
paged kernel's online softmax, the program's dense dispatch over all
experts); read 5.4e-7 to 8.6e-7 at most over four seeds, with logits of
order 1. A routing fault moves them by their own scale: top-1 in place of
top-2 read 0.58 to 0.90, and the top-k probabilities left unrenormalised
0.39 to 0.64. ``MAX_ABS`` 1e-4 lies a hundred times above the first and
three thousand times below the second.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec
from harness import weights as W

CONFIG = {
    "hidden_size": 128, "intermediate_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "tie_word_embeddings": False,
    "torch_dtype": "float32", "serving": {"family": "moe", "block_size": 16}}
PROMPTS = (40, 23)
STEPS = 8
MAX_ABS = 1e-4


def program_logits(model, cfg, params, prompts):
    """Per request, the logits of its last prompt position and of each
    decode step, and the greedy tokens they chose."""
    from repro.serving.kv_cache import PagedKVCache, spec_for_model

    kv = PagedKVCache(spec_for_model(cfg, 32))
    rows = [[] for _ in prompts]
    toks = [[] for _ in prompts]
    for i, p in enumerate(prompts):
        kv.bm.allocate(i, len(p) + STEPS)
        logits, cache = model.prefill(params, {"tokens": jnp.asarray([p], jnp.int32)})
        kv.write_prefill(i, cache["k"][:, 0], cache["v"][:, 0], len(p))
        rows[i].append(np.asarray(logits[0], np.float32))
        toks[i].append(int(np.argmax(rows[i][-1])))
    tables = jnp.asarray(kv.export_block_tables(range(len(prompts))))
    for step in range(STEPS - 1):
        tok = jnp.asarray([t[-1] for t in toks], jnp.int32)
        lens = jnp.asarray([len(p) + step for p in prompts], jnp.int32)
        logits, kv.pool = model.decode_paged(params, tok, kv.pool, tables, lens)
        for i in range(len(prompts)):
            rows[i].append(np.asarray(logits[i], np.float32))
            toks[i].append(int(np.argmax(rows[i][-1])))
    return [np.stack(r) for r in rows], toks


@pytest.fixture(scope="module")
def moe():
    from repro.models.api import get_model

    fam = spec.family("moe")
    cfg = fam.program_config(CONFIG, "moe-small")
    return fam, fam.shape(CONFIG), cfg, get_model(cfg)


@pytest.mark.parametrize("seed", [3, 2**31 + 99, 2**32 + 17])
def test_moe_reference_matches_the_programs_logits(moe, seed):
    fam, shape, cfg, model = moe
    params = W.make(shape, seed)
    rng = np.random.default_rng(seed % 1000)
    prompts = [list(rng.integers(0, CONFIG["vocab_size"], n)) for n in PROMPTS]
    got, toks = program_logits(model, cfg, params, prompts)
    for p, g, t in zip(prompts, got, toks):
        ref = fam.logits(params, shape, np.asarray(p + t[:-1], np.int32), len(p) - 1)
        assert g.shape == ref.shape == (STEPS, CONFIG["vocab_size"])
        assert np.abs(g - ref).max() <= MAX_ABS
