"""What decides ``correct``, at a size a test run holds, on the CPU.

A whole run of the harness (the look for a chip skipped) on a small dense
model: a sound run passes; the control, the float32 reference computed in
float8, fails the same limit under the same rule; and each fault a serving
cell can have, planted in the timed path underneath (a token altered, the
decode state left unchanged, half of each batch left out, the P->D exchange
left out), turns ``correct`` false.

The limit here is this size's own, set as the cells' are: sound runs read
widest gaps of at most 0.007 on six seeds, the float8 control at least 0.064
(CPU, this configuration), so 0.03.
"""
import jax
import jax.numpy as jnp
import pytest

from harness import spec
from harness.main import compare, run_cell, served

LIMIT = 0.03
CONFIG = {
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 2048,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
    "serving": {"family": "dense", "qk_norm": True, "block_size": 32, "max_batch_tokens": 64,
                "max_decode_batch": 2, "pool_blocks": 24}}
MIX = {"loop": "closed", "clients": {"pool_quantile": 0.9, "min": 2},
       "components": [{"name": "a", "weight": 1, "prompt": {"normal": [96, 30]},
                       "output": {"normal": [12, 3]}}],
       "prompt_clip": [32, 128], "output_clip": [6, 16],
       "requests": 50, "population_seed": 3}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def cell():
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield spec.Cell("tiny", 1, "tiny", CONFIG, "tiny", MIX,
                    {"max_logit_gap": {"limit": LIMIT}, "sample": {"tokens": 40, "max_requests": 4}},
                    [], [], 10)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


@pytest.fixture(autouse=True)
def no_cache_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def run(cell, fault=None, seed=2**31 + 3):
    return run_cell(cell, seed, 2.0, False, jax.devices(), PEAKS, say=lambda m: None,
                    fault=fault)


def decode_engine(sut):
    return sut.client.cluster.engines[1]


def alter_token(sut):
    """A served token altered where it is produced."""
    eng = decode_engine(sut)
    inner = eng.run_decode

    def run_decode(decision):
        out = inner(decision)
        for req in decision.decode_batch[:1]:
            req.output_tokens[-1] = (req.output_tokens[-1] + 1) % CONFIG["vocab_size"]
        return out
    eng.run_decode = run_decode


def state_unchanged(sut):
    """The decode step returns its pool unchanged: no token's K/V lands."""
    eng = decode_engine(sut)
    step = eng._paged_step
    eng._paged_step = lambda params, tok, pool, bt, lens: (step(params, tok, pool, bt, lens)[0], pool)


def half_batch(sut):
    """Half of each decode batch left out: its rows take the first row's logits."""
    eng = decode_engine(sut)
    step = eng._paged_step

    def broken(params, tok, pool, bt, lens):
        logits, pool = step(params, tok, pool, bt, lens)
        b = logits.shape[0]
        return logits.at[b // 2:].set(jnp.broadcast_to(logits[:1], logits[b // 2:].shape)), pool
    eng._paged_step = broken


def transfer_left_out(sut):
    """The P->D exchange left out: no page reaches the decode pool, and the
    check of the moved pages is skipped with it."""
    sut.client.cluster._attempt_unit = lambda req, src, dst, execute, plan: 0.0


def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] <= LIMIT
    assert list(res["checks"]) == ["max_logit_gap", "failed_requests"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [alter_token, state_unchanged, half_batch, transfer_left_out],
                         ids=["token_altered", "state_unchanged", "half_batch",
                              "transfer_left_out"])
def test_fault_makes_run_incorrect(cell, fault):
    res = run(cell, fault)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT


def test_float8_control_fails_the_limit(cell):
    from harness import check
    from harness.main import Cluster
    sut = Cluster(cell, 7)
    drv = sut.load(7)
    drv.lead_in(120.0)
    w0, _ = drv.run(2.0)
    drv.until_finished(w0, 30.0)
    sampled = served(check.sample(drv.records, w0, 7, 40, 4))
    sut.recorder.close()
    gaps = compare(cell, sut.weights, sut.shape, sampled, fp8=True)
    assert check.judge(gaps["served"], LIMIT, 0)
    assert not check.judge(gaps["control"], LIMIT, 0)
    assert gaps["served"] <= LIMIT < gaps["control"]
