"""Sharding rules: logical-axis mapping + divisibility fallbacks, and a real
1-device-mesh execution of the jitted train/serve steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.distributed import sharding as SH
from repro.distributed import steps as ST
from repro.launch.mesh import make_local_mesh
from repro.models.api import get_model, input_specs
from repro.training import optimizer as OPT


@pytest.fixture(scope="module")
def mesh11():
    return make_local_mesh(data=1, model=1)


def _fake_mesh(shape, names):
    """Mesh stand-in exposing axis_names/devices.shape for spec tests."""
    class M:
        axis_names = names
        class devices:
            pass
    M.devices = np.zeros(shape)
    return M


def test_spec_for_divisible_dims():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    assert SH.spec_for((256, 4096), ("batch", "embed"), mesh) == P("data")
    assert SH.spec_for((4096, 32, 128), ("embed", "heads", "head_dim"), mesh) \
        == P(None, "model")
    # vocab not divisible -> replicated
    assert SH.spec_for((49155, 1024), ("vocab", "embed"), mesh) == P()


def test_spec_for_fallback_kv_seq():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    # kv_heads=8 can't shard over model=16 -> kv_seq takes the model axis
    spec = SH.spec_for((32, 128, 32768, 8, 128),
                       ("layers", "batch", "kv_seq", "kv_heads", "head_dim"), mesh)
    assert spec == P(None, "data", "model")
    # kv=16 divides: kv_seq grabs model first (dim order), kv replicated
    spec2 = SH.spec_for((24, 128, 32768, 16, 64),
                        ("layers", "batch", "kv_seq", "kv_heads", "head_dim"), mesh)
    assert spec2 == P(None, "data", "model")


def test_paged_pool_never_shards_pages():
    """A FLOWKV pool's page dim is pinned to replication: page ids are global
    names shared by every shard's block manager and descriptor table, so the
    kv_seq fallback must never grab the block dim — even when num_blocks
    happens to divide the model axis."""
    mesh = _fake_mesh((16, 16), ("data", "model"))
    # num_blocks=4096 divides model=16: under the kv_seq fallback this dim
    # WOULD shard — kv_pages pins it replicated
    spec = SH.spec_for((4096, 32, 2, 8, 32, 128), SH.PAGED_POOL_AXES, mesh)
    assert spec == P()
    # misdeclaring the page dim as kv_seq is exactly the regression guarded
    # against: it silently splits the page address space
    bad = SH.spec_for((4096, 32, 2, 8, 32, 128),
                      ("kv_seq", "layers", None, None, None, None), mesh)
    assert bad == P("model")
    # the declared "kv_pages" rule must exist and be an empty candidate list
    # (intent recorded, not merely absent)
    assert SH.DEFAULT_RULES["kv_pages"] == ()


def test_spec_for_multipod_batch():
    mesh = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
    assert SH.spec_for((256, 4096), ("batch", "seq"), mesh) == P(("pod", "data"))
    # batch=1 (long_500k): replicated
    assert SH.spec_for((1, 131072), ("batch", "seq"), mesh) == P()


def test_zero1_extends_specs():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    shapes = {"w": jax.ShapeDtypeStruct((4096, 16384), jnp.float32)}
    p_spec = {"w": P(None, "model")}
    z = ST.zero1_specs(shapes, p_spec, mesh)
    assert z["w"] == P("data", "model")


def test_train_step_runs_and_learns(mesh11):
    cfg = get_smoke_config("qwen3-1.7b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    state = OPT.init_state(params)
    train_step, state_spec = ST.make_train_step(
        model, mesh11, jax.eval_shape(lambda: params),
        opt_cfg=OPT.AdamWConfig(lr=1e-2, warmup_steps=1))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 16))),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 16)))}
    batch["labels"] = batch["tokens"]     # learn to copy
    step = jax.jit(train_step, donate_argnums=(0,))
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert int(state["step"]) == 8


def test_decode_step_jitted_consistency(mesh11):
    cfg = get_smoke_config("minitron-8b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.ones((2, 6), jnp.int32)
    logits, pre = model.prefill(params, {"tokens": toks})
    cache = model.init_cache(2, 10)
    cache["k"] = cache["k"].at[:, :, :6].set(pre["k"])
    cache["v"] = cache["v"].at[:, :, :6].set(pre["v"])
    cache["length"] = jnp.full((2,), 6, jnp.int32)
    decode = jax.jit(ST.make_decode_step(model, mesh11))
    lg1, c1 = decode(params, jnp.zeros((2,), jnp.int32), cache)
    lg2, _ = model.decode(params, jnp.zeros((2,), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2), rtol=1e-5, atol=1e-5)


def test_input_specs_all_cells():
    """input_specs must produce spec/axes trees for every applicable cell."""
    from repro.configs import ASSIGNED_ARCHS, SHAPES, shape_applicable
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape_name, (kind, seq, batch) in SHAPES.items():
            ok, _ = shape_applicable(cfg, shape_name)
            if not ok:
                continue
            specs, axes = input_specs(cfg, kind, seq, batch)
            flat_s = jax.tree.leaves(specs)
            assert flat_s, (arch, shape_name)
            for leaf in flat_s:
                assert all(d > 0 for d in leaf.shape)


def test_gradient_compression_error_feedback():
    params = {"w": jnp.ones((8, 8)) * 0.3}
    grads = {"w": jnp.asarray(np.random.RandomState(0).randn(8, 8), jnp.float32)}
    ef = OPT.init_error_feedback(params)
    q, scales, ef = OPT.compress_grads(grads, ef)
    deq = OPT.decompress_grads(q, scales)
    err1 = float(jnp.abs(deq["w"] - grads["w"]).max())
    assert q["w"].dtype == jnp.int8
    assert err1 < float(jnp.abs(grads["w"]).max()) / 64     # <= quant step
    # residual carries the rounding error
    np.testing.assert_allclose(np.asarray(ef["w"]),
                               np.asarray(grads["w"] - deq["w"]), rtol=1e-5, atol=1e-6)
