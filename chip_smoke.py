"""Smoke run of FlowKV's disaggregated serving path on one TPU chip.

    python chip_smoke.py [--seed N]

Run from the root of a checkout. It builds qwen3-1.7b at its published
widths (28 layers, d_model 2048, bf16, random weights made from ``--seed``),
serves four requests through ``FlowKVClient`` with one prefill node and one
decode node — chunked prefill, a prefix-cache hit, the FlowKV
descriptor-table transfer, paged decode — and checks that:

* every request finished and every transfer's checksum verified;
* decode ran the one-dispatch paged kernel step (``decode_dispatches ==
  decode_steps``), and the compiled decode step and transfer executor hold
  Mosaic kernels (``tpu_custom_call``), not the Pallas interpreter;
* ``kv_transfer`` is bit-exact and ``paged_decode_attention`` is within
  tolerance against their ``ref.py`` oracles on the device;
* every generated token is a near-argmax of a float32 teacher-forced
  forward of the same model.

All work happens in this one process, which holds the chip. With no TPU it
exits non-zero before any phase. The timings it prints are those of a smoke
run, not benchmark numbers. The last line of standard output is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen3-1.7b"
PREFIX_TOKENS = 1024           # shared by the 1,500- and 3,000-token prompts
PROMPT_LENGTHS = (64, 700, 1500, 3000)
NEW_TOKENS = 32
# paged attention vs its float32 oracle: the kernel's output is rounded to
# bf16 (2**-9 relative) after a p @ v product whose p may itself be taken at
# bf16 on the MXU; the softmax state (m, l) stays float32 throughout
ATTN_OUT_TOL = 2.0 ** -6
ATTN_STATS_TOL = 1e-4


def _compile_seconds():
    """Running total of backend compile (or persistent-cache load) seconds."""
    total = [0.0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration
    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def check(ok, what) -> None:
    """Fail the run (an assert would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _has_mosaic(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def check_kernels(cfg, key) -> None:
    """Kernels on the device against their ``ref.py`` oracles, at cfg widths."""
    from repro.kernels.kv_gather import kv_transfer, kv_transfer_ref
    from repro.kernels.paged_attention import (
        paged_decode_attention, paged_decode_attention_stats_ref)

    L, KV, hd, bs = (cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
                     cfg.block_size)
    payload = bs * KV * hd
    nb = 128                     # holds the longest prompt's 94 pages
    k = jax.random.split(key, 6)
    src = jax.random.normal(k[0], (nb, L, 2, KV, bs, hd), cfg.dtype)
    dst = jax.random.normal(k[1], (nb, L, 2, KV, bs, hd), cfg.dtype)
    n = 3 * L * 2
    sp = jax.random.permutation(k[2], nb * L * 2)[:n].astype(jnp.int32)
    dp = jax.random.permutation(k[3], nb * L * 2)[:n].astype(jnp.int32)
    transfer = jax.jit(kv_transfer)
    check(_has_mosaic(transfer, src, dst, sp, dp), "kv_transfer not Mosaic")
    got = transfer(src, dst, sp, dp)
    check(bool(jnp.array_equal(got, kv_transfer_ref(src, dst, sp, dp))),
          "kv_transfer differs from kv_transfer_ref")
    print(f"kv_transfer: {n} pages of {payload} elements, bit-exact vs ref")

    lengths = jnp.asarray(PROMPT_LENGTHS, jnp.int32)
    b = lengths.shape[0]
    maxb = -(-max(PROMPT_LENGTHS) // bs)
    bt = jax.random.permutation(k[4], nb)[:maxb]
    bt = jnp.tile(bt, (b, 1)).astype(jnp.int32)
    q = jax.random.normal(k[5], (b, cfg.num_heads, hd), cfg.dtype)
    attend = jax.jit(lambda *a: paged_decode_attention(
        *a, return_stats=True))
    check(_has_mosaic(attend, q, src, 0, bt, lengths),
          "paged_decode_attention not Mosaic")
    out, m, l = attend(q, src, 0, bt, lengths)
    with jax.default_matmul_precision("highest"):
        r_out, r_m, r_l = paged_decode_attention_stats_ref(
            q.astype(jnp.float32), src[:, :1].astype(jnp.float32), 0, bt,
            lengths)
    for name, a, r, tol in (("out", out, r_out, ATTN_OUT_TOL),
                            ("m", m, r_m, ATTN_STATS_TOL),
                            ("l", l, r_l, ATTN_STATS_TOL)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        err = float(np.max(np.abs(a - r) / (tol + tol * np.abs(r))))
        print(f"paged_decode_attention {name}: max |got-ref| = "
              f"{float(np.max(np.abs(a - r)))} (tolerance {tol} abs + rel)")
        check(err <= 1.0, f"paged_decode_attention {name} outside tolerance")


def serve(cfg, params, seed: int):
    """Four requests through FlowKVClient (1 prefill node, 1 decode node).

    The 1,500-token prompt is admitted with the 64- and 700-token ones, so
    the shared 2,048-token prefill budget splits it into two chunks. The
    3,000-token prompt, which shares its first 1,024 tokens with it, is
    submitted once that prefix is resident, so it takes a prefix hit.
    """
    from repro.serving.api import FlowKVClient
    from repro.serving.request import RequestState, SamplingParams

    rng = np.random.RandomState(seed)
    draw = lambda n: rng.randint(0, cfg.vocab_size, size=n).tolist()
    prefix = draw(PREFIX_TOKENS)
    short, medium, donor_len, follower_len = PROMPT_LENGTHS
    prompts = [draw(short), draw(medium), prefix + draw(donor_len - PREFIX_TOKENS)]
    follower = prefix + draw(follower_len - PREFIX_TOKENS)

    client = FlowKVClient(cfg, params, num_prefill=1, num_decode=1,
                          transfer_schedule="flowkv")
    sampling = SamplingParams(max_new_tokens=NEW_TOKENS)
    handles = [client.submit(p, sampling) for p in prompts]
    donor = handles[2].request
    for _ in range(100):
        if donor.state is RequestState.DECODING:
            break
        client.step()
    check(donor.state is RequestState.DECODING,
          f"the 1,500-token prompt never reached decode: {donor.state}")
    handles.append(client.submit(follower, sampling))
    client.drain(max_cycles=500)
    return client, handles


def check_serving(client, handles) -> None:
    from repro.core.transfer import _get_executor
    from repro.kernels import interpret_mode

    cluster = client.cluster
    stats = client.stats()
    print("serving stats:", json.dumps(
        {k: stats[k] for k in ("finished", "transfers", "prefix_hits",
                               "prefix_tokens_reused", "prefix_fetches",
                               "prefill_tokens_computed", "decode_steps",
                               "decode_dispatches",
                               "decode_compile_variants")}))
    for h in handles:
        check(h.done and h.request.num_output == NEW_TOKENS,
              f"request {h.request_id} {h.state}, "
              f"{h.request.num_output} tokens")
    check(stats["finished"] == len(handles), "not every request finished")
    donor = handles[2].request
    check(donor.last_prefill_chunk_tokens < donor.prompt_len,
          "the 1,500-token prompt was not chunked")
    check(stats["prefix_hits"] >= 1
          and stats["prefix_tokens_reused"] >= PREFIX_TOKENS,
          "the shared prefix was not reused")
    # every transfer ran its checksum (PDCluster._attempt_unit) and passed
    # on the first attempt: no retry, no degrade to recompute
    check(cluster.transfers and all(
        t.status == "ok" and t.retries == 0 for t in cluster.transfers),
        [dataclasses.asdict(t) for t in cluster.transfers])
    check(cluster.transfer_retry_count == 0
          and cluster.degraded_to_recompute == 0, "a transfer was retried")
    check(stats["decode_steps"] > 0
          and stats["decode_dispatches"] == stats["decode_steps"],
          "decode left the one-dispatch paged kernel step")

    src, dst = cluster.engines[0], cluster.engines[1]
    pages = jnp.arange(2, dtype=jnp.int32)
    executor = _get_executor(src.kv.spec, dst.kv.spec, "flowkv",
                             interpret_mode())
    check(_has_mosaic(executor, src.kv.pool, dst.kv.pool, pages, pages),
          "transfer executor holds no Mosaic kernel")
    b, w = 2, 4
    check(dst.use_paged_decode and _has_mosaic(
        dst._paged_step, dst.params, jnp.zeros((b,), jnp.int32), dst.kv.pool,
        jnp.zeros((b, w), jnp.int32), jnp.ones((b,), jnp.int32)),
        "decode step holds no Mosaic kernel")
    print("transfer executor and decode step: compiled Mosaic kernels")


def reference_logits(params, cfg, tokens, n_last: int) -> np.ndarray:
    """Float32 teacher-forced forward of the dense model; logits of the
    last ``n_last`` positions. Weights stay bf16 in memory and are widened
    layer by layer; matmuls run at full float32 precision."""
    from repro.models import transformer
    from repro.models.common import embed, rms_norm, unembed

    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    @jax.jit
    def forward(params, tokens):
        x = embed(tokens, params["embed"].astype(jnp.float32))
        positions = jnp.arange(tokens.shape[1])[None, :]

        def body(h, lp):
            h, _, _, _ = transformer._layer_train(cfg32, h, f32(lp), positions)
            return h, None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = rms_norm(x[:, -n_last:], params["final_norm"], cfg.norm_eps)
        table = params.get("unembed", params["embed"]).astype(jnp.float32)
        return unembed(x, table)[0]

    with jax.default_matmul_precision("highest"):
        return np.asarray(forward(params, jnp.asarray([tokens], jnp.int32)))


def check_tokens(cfg, params, handles) -> None:
    """Each served token must be within delta of the float32 argmax.

    bf16 keeps 8 significant bits (unit roundoff u = 2**-9). The served
    path rounds the residual stream twice per layer, so about 2L roundings
    reach the last hidden state; their random-signed sum is about
    sqrt(2L) u of it, and each logit inherits that share of the logit
    scale (the std over the vocabulary). delta allows 8x that for growth
    through softmax and SwiGLU: 8 sqrt(2L) u std.
    """
    u = 2.0 ** -9
    factor = 8.0 * math.sqrt(2 * cfg.num_layers) * u
    worst = 0.0
    for h in handles:
        req = h.request
        out = req.output_tokens
        ref = reference_logits(params, cfg, req.prompt_tokens + out[:-1],
                               len(out))
        std = ref.std(axis=-1)
        gap = ref.max(axis=-1) - ref[np.arange(len(out)), out]
        ratio = gap / std
        worst = max(worst, float(ratio.max()))
        print(f"request {req.request_id}: prompt {req.prompt_len}, "
              f"{len(out)} tokens, ref-logit std {float(std.mean())}, "
              f"spread {float((ref.max(-1) - ref.min(-1)).mean())}, "
              f"worst gap {float(ratio.max())} std "
              f"(delta {factor} std), exact argmax "
              f"{int((gap == 0).sum())}/{len(out)}")
        check(np.all(gap <= factor * std),
              f"request {req.request_id}: a token is not a near-argmax")
    print(f"near-argmax: every token within delta; worst gap {worst} std")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; devices {devices}; "
          f"device_kind {dev.device_kind!r}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.api import get_model

    print("compile cache:", enable_compile_cache())
    compile_s = _compile_seconds()
    t0 = time.monotonic()
    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(args.seed)
    check_kernels(cfg, jax.random.fold_in(key, 1))
    params = get_model(cfg).init(key)
    client, handles = serve(cfg, params, args.seed)
    check_serving(client, handles)
    check_tokens(cfg, params, handles)
    wall = time.monotonic() - t0
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"smoke run (not a benchmark): wall {wall} s, compile {compile_s()} s,"
          f" peak_bytes_in_use {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
