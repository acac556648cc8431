"""System-level behaviour tests: flash reference paths, prefix cache, HLO
analyzers, and a miniature multi-device dry-run (subprocess, 8 host devices)."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import attend, causal_mask
from repro.models.flash import flash_attention
from repro.serving.prefix_cache import PrefixCacheIndex

REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# flash reference paths (the dry-run's attention lowering)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kwargs", [
    dict(q_chunk=32, kv_chunk=16),
    dict(q_chunk=32, wedge=True),
    dict(window=12, q_chunk=16),
    dict(q_chunk=37, kv_chunk=53),          # non-divisible chunking
])
def test_flash_matches_direct(kwargs):
    B, S, H, KV, HD = 2, 100, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, HD))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, HD))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, HD))
    window = kwargs.get("window", 0)
    ref = attend(q, k, v, causal_mask(S, S, 0, window)[None, None, None])
    out = flash_attention(q, k, v, causal=True, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)


def test_flash_differentiable():
    B, S, H, KV, HD = 1, 64, 2, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, HD))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, HD))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, HD))
    g = jax.grad(lambda q: flash_attention(q, k, v, q_chunk=16, kv_chunk=16).sum())(q)
    assert bool(jnp.all(jnp.isfinite(g)))


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------
def test_prefix_cache_block_granularity():
    idx = PrefixCacheIndex(block_size=4)
    idx.insert(0, list(range(10)))          # 2 full blocks cached
    assert idx.match(0, list(range(10))) == 8
    assert idx.match(0, list(range(6))) == 4
    assert idx.match(0, [99] * 8) == 0
    assert idx.match(1, list(range(10))) == 0
    best = idx.best_nodes(list(range(10)))
    assert best[0] == (0, 8)
    idx.evict_node(0)
    assert idx.match(0, list(range(10))) == 0


def test_prefix_cache_divergent_suffix():
    idx = PrefixCacheIndex(block_size=4)
    idx.insert(2, [1, 2, 3, 4, 5, 6, 7, 8])
    probe = [1, 2, 3, 4, 9, 9, 9, 9]
    assert idx.match(2, probe) == 4          # first block matches, second not


# ---------------------------------------------------------------------------
# HLO analyzers
# ---------------------------------------------------------------------------
def test_hlo_flops_counts_nested_scans():
    from repro.launch.hlo_flops import analyze_hlo
    A = jnp.zeros((128, 128))

    def inner(x, _):
        return x @ A, None

    def outer(x, _):
        y, _ = jax.lax.scan(inner, x, None, length=7)
        return y, None

    def f(x):
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    compiled = jax.jit(f).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    c = analyze_hlo(compiled.as_text())
    expected = 2 * 128 ** 3 * 21
    assert abs(c.flops - expected) / expected < 0.01
    assert c.unknown_trip_counts == 0


def test_collective_parse_on_psum():
    from repro.launch.hlo_flops import analyze_hlo
    # single-device psum lowers away; just exercise the parser on real HLO
    compiled = jax.jit(lambda x: x * 2 + 1).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    c = analyze_hlo(compiled.as_text())
    assert c.collective_total == 0


# ---------------------------------------------------------------------------
# miniature dry-run: 8 forced host devices, (2, 2, 2) pod mesh, smoke arch
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_mini_multipod_dryrun():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, json
        from jax.sharding import NamedSharding
        from repro.configs import get_smoke_config
        from repro.distributed import sharding as SH, steps as ST
        from repro.models.api import get_model, input_specs
        from repro.training import optimizer as OPT

        cfg = get_smoke_config("minitron-8b")
        model = get_model(cfg)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        state = ST.abstract_train_state(model)
        train_step, state_spec = ST.make_train_step(model, mesh, state["params"])
        specs, axes = input_specs(cfg, "train", 16, 8)
        b_spec = SH.tree_specs(specs, axes, mesh)
        ns = lambda s: NamedSharding(mesh, s)
        fn = jax.jit(train_step,
                     in_shardings=(jax.tree.map(ns, state_spec), jax.tree.map(ns, b_spec)),
                     out_shardings=(jax.tree.map(ns, state_spec), None))
        compiled = fn.lower(state, specs).compile()
        # it must ACTUALLY run on the 8-device mesh too
        params = model.init(jax.random.PRNGKey(0))
        real = OPT.init_state(params)
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                 "labels": jnp.zeros((8, 16), jnp.int32)}
        out_state, metrics = fn(real, batch)
        print(json.dumps({"loss": float(metrics["loss"]),
                          "devices": jax.device_count()}))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(REPO / "src")}, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["devices"] == 8
    assert result["loss"] > 0 and result["loss"] < 20


def test_compile_cache_dir_honours_env(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise a fixed checkout
    path. jax.config.update is captured, so the cache stays off here."""
    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
