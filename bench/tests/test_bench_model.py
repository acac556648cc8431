"""The benchmark's model arithmetic, as the family modules
(``reference/<family>.py``) give it, for the dense configuration and for a
mixture of experts at granite-moe-1b-a400m's published sizes: FLOP counts
against hand counts, and the weight tree against the program's."""
import json
import os

import jax
import pytest

from harness import spec
from harness import weights as W
from harness.spec import load_module

LEAF = lambda x: isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


# granite-3.0-1b-a400m-base's published sizes (huggingface.co/ibm-granite):
# the arithmetic of a mixture of experts, checked by hand below.
GRANITE = {
    "hidden_size": 1024, "intermediate_size": 512, "num_hidden_layers": 24,
    "num_attention_heads": 16, "num_key_value_heads": 8, "num_local_experts": 32,
    "num_experts_per_tok": 8, "vocab_size": 49155, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "serving": {"family": "moe", "head_dim": 64, "block_size": 32}}


def config(name):
    if name == "granite-moe-1b-a400m":
        return GRANITE
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def shape(name):
    return spec.model_shape(config(name))


def family(name):
    return spec.family(shape(name)["family"])


def test_qwen3_flops_by_hand():
    s = shape("qwen3-1.7b")
    model = family("qwen3-1.7b")
    # per layer: q,k,v,o = 4 * 2048*2048 / (k,v at 1024 wide: 2*2048*1024) -> 12,582,912
    # SwiGLU 3 * 2048 * 6144 = 37,748,736
    assert model.layer_params(s) == 12_582_912 + 37_748_736
    # one token with nothing cached: 28 layers of (2 * 50,331,648 + 4*16*128*1)
    # plus the tied logits 2 * 151,936 * 2,048
    assert model.decode_flops(s, 0) == 28 * (100_663_296 + 8_192) + 622_329_856
    # a 2,048-token chunk after 2,048 cached: pairs 2048*2048 + 2048*2049/2
    pairs = 2048 * 2048 + 2048 * 2049 // 2
    assert model.prefill_flops(s, 2048, 2048) == \
        28 * (2 * 50_331_648 * 2048 + 4 * 16 * 128 * pairs) + 622_329_856


def test_granite_flops_count_top8_experts():
    s = shape("granite-moe-1b-a400m")
    model = family("granite-moe-1b-a400m")
    # attention 3 * 1024*1024 + ... = 3,145,728; router 1024*32; 8 experts of 3*1024*512
    assert model.layer_params(s) == 3_145_728 + 32_768 + 12_582_912
    assert model.decode_flops(s, 0) == 24 * (2 * 15_761_408 + 4_096) + 2 * 49_155 * 1_024
    # active parameters about 0.43 B: two FLOPs each
    assert model.decode_flops(s, 0) == pytest.approx(2 * 0.43e9, rel=0.01)


@pytest.mark.parametrize("name", ["qwen3-1.7b", "granite-moe-1b-a400m"])
def test_weight_tree_is_the_programs(name):
    from repro.models.api import get_model
    cfg = family(name).program_config(config(name), name)
    want = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    tree = family(name).weight_shapes(shape(name))
    assert jax.tree.structure(want) == jax.tree.structure(
        jax.tree.map(lambda x: 0, tree, is_leaf=LEAF))
    assert [w.shape for w in jax.tree.leaves(want)] == \
        [t[0] for t in jax.tree.leaves(tree, is_leaf=LEAF)]


def test_weights_are_made_from_the_seed():
    s = dict(shape("granite-moe-1b-a400m"), layers=1, vocab=64, d_model=32, heads=4,
             kv_heads=2, head_dim=8, d_ff=16, experts=4, top_k=2)
    a, b, c = W.make(s, 2**33 + 1), W.make(s, 2**33 + 1), W.make(s, 2**33 + 2)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["embed"] == c["embed"]).all())
    assert a["layers"]["moe_w_gate"].dtype == jax.numpy.bfloat16


def test_paged_attention_work_by_hand_for_both_configurations():
    attn = load_module("metrics", "paged_attn_roofline")
    # one full-attention call a layer, 8 KV heads in groups of 2, head_dim 128
    assert family("qwen3-1.7b").attention_calls(shape("qwen3-1.7b")) == [(8, 2, 128, 0)] * 28
    q = family("qwen3-1.7b").attention_calls(shape("qwen3-1.7b"))[0]
    # 4,096 cached tokens: scores and weighted sum 4 x 16 heads x 128 per token
    assert attn.call_flops(q, [4096]) == 4 * 16 * 128 * 4096
    # K and V of 4,096 tokens at 8 heads of 128 in bf16, plus q, out, m and l
    assert attn.call_bytes(q, [4096]) == 16_777_216 + 2 * 16 * 128 * 2 + 2 * 16 * 4
    g = family("granite-moe-1b-a400m").attention_calls(shape("granite-moe-1b-a400m"))[0]
    assert attn.call_bytes(g, [1000, 24]) == 2 * 8 * 64 * 1024 * 2 + 2 * (2 * 16 * 64 * 2 + 2 * 16 * 4)
    # memory-bound on a v5e: 2 FLOPs a byte against a ridge of 240
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert attn.least_s(q, [4096], peaks) == attn.call_bytes(q, [4096]) / 819e9


def test_transfer_bytes_by_hand_for_both_configurations():
    xfer = load_module("metrics", "kv_transfer_roofline")
    # one qwen3 request of 4,096 tokens: 128 blocks x 28 layers x K,V pages of
    # 32 x 8 x 128 elements, viewed as 256 rows of 128 lanes: 114,688 B a token
    assert xfer.call_bytes(128 * 28 * 2, 256, 128) == 2 * 4096 * 114_688
    # granite: pages of 32 x 8 x 64 = 128 rows of 128 lanes, 49,152 B a token
    assert xfer.call_bytes(128 * 24 * 2, 128, 128) == 2 * 4096 * 49_152
