"""Simulator: paper-trend assertions (FlowKV's wins must emerge from the
real control plane + calibrated costs, not be hard-coded)."""
import dataclasses

import pytest

from repro.configs import get_config
from repro.sim.cluster_sim import ROUTING_POLICIES, ClusterSim
from repro.sim.hardware import A100, H20, L20
from repro.sim.scenarios import SCENARIOS, get_scenario
from repro.sim.workload import (LONGBENCH, SIMULATED, WorkloadSpec, generate,
                                generate_mixture)


@pytest.fixture(scope="module")
def cfg8b():
    return get_config("llama31-8b")


def _run(cfg, kind, wl="10k", rps=1.0, **kw):
    sim = ClusterSim(cfg, kind, **kw)
    return sim.run(generate(SIMULATED[wl], rps=rps, seed=0), t_max=50_000)


def test_flowkv_beats_vllm_disagg_at_load(cfg8b):
    fk = _run(cfg8b, "flowkv")
    vd = _run(cfg8b, "vllm_disagg")
    assert fk["finished"] == vd["finished"] == 100
    assert fk["throughput_tok_s"] > 1.2 * vd["throughput_tok_s"]
    assert fk["mean_transfer_calls"] == 1.0
    assert vd["mean_transfer_calls"] == 64.0          # 2L for llama31-8b


def test_flowkv_transfer_latency_negligible(cfg8b):
    fk = _run(cfg8b, "flowkv")
    # paper: ~0.053 s average; must be well under 100 ms at 10k ctx
    assert fk["mean_transfer_s"] < 0.1
    vd = _run(cfg8b, "vllm_disagg")
    assert vd["mean_transfer_s"] > 10 * fk["mean_transfer_s"]


def test_distserve_saturates_on_long_prompts(cfg8b):
    mid = _run(cfg8b, "distserve", rps=1.0)
    hi = _run(cfg8b, "distserve", rps=2.0)
    # saturation plateau: doubling RPS past saturation changes nothing
    assert abs(hi["throughput_tok_s"] - mid["throughput_tok_s"]) \
        < 0.2 * mid["throughput_tok_s"]
    fk = _run(cfg8b, "flowkv", rps=2.0)
    assert fk["throughput_tok_s"] > 1.4 * hi["throughput_tok_s"]


def test_colocated_tpot_degrades_under_long_prefill(cfg8b):
    colo = _run(cfg8b, "vllm_colocated", rps=1.0)
    disagg = _run(cfg8b, "flowkv", rps=1.0)
    assert colo["mean_tpot_s"] > disagg["mean_tpot_s"]


def test_heterogeneous_placement_gain(cfg8b):
    wl = LONGBENCH["gov_report"]
    good = ClusterSim(cfg8b, "flowkv", num_prefill=4, num_decode=4,
                      hw_prefill=L20, hw_decode=H20, same_host=False)
    g = good.run(generate(wl, rps=0.5, seed=1), t_max=50_000)
    bad = ClusterSim(cfg8b, "flowkv", num_prefill=4, num_decode=4,
                     hw_prefill=H20, hw_decode=L20, same_host=False)
    b = bad.run(generate(wl, rps=0.5, seed=1), t_max=50_000)
    assert g["mean_e2e_s"] < b["mean_e2e_s"], (g["mean_e2e_s"], b["mean_e2e_s"])


def test_role_switch_fires_under_imbalance(cfg8b):
    sim = ClusterSim(cfg8b, "flowkv", num_prefill=1, num_decode=3)
    stats = sim.run(generate(SIMULATED["10k"], rps=2.0, seed=0), t_max=50_000)
    kinds = {e.kind for e in sim.controller.events}
    assert "role_switch" in kinds or "regime" in kinds


def test_sim_dispatch_counts_from_descriptor_tables(cfg8b):
    """The simulator's dispatch metric comes from the same descriptor tables
    the real executor runs: one dispatch per transfer, every system."""
    for kind in ("flowkv", "vllm_disagg"):
        stats = _run(cfg8b, kind)
        assert stats["mean_transfer_dispatches"] == 1.0, kind


# ---------------------------------------------------------------------------
# scenario suite plumbing (benchmarks/scenarios.py runs the full gates)
# ---------------------------------------------------------------------------
def test_baseline_routing_policies_are_passive(cfg8b):
    """round_robin / static_pd must not leak load-aware behavior."""
    wl = dataclasses.replace(SIMULATED["1k"], num_requests=20)
    for routing in ("round_robin", "static_pd"):
        sim = ClusterSim(cfg8b, "flowkv", num_prefill=1, num_decode=3,
                         routing=routing)
        assert not sim.controller.actions_enabled
        stats = sim.run(generate(wl, rps=3.0, seed=0), t_max=20_000)
        assert stats["finished"] == 20
        kinds = {e.kind for e in sim.controller.events}
        assert "role_switch" not in kinds and "set_role" not in kinds
    with pytest.raises(ValueError, match="routing"):
        ClusterSim(cfg8b, "flowkv", routing="bogus")


def test_round_robin_rotates_both_sides(cfg8b):
    wl = dataclasses.replace(SIMULATED["1k"], num_requests=8)
    sim = ClusterSim(cfg8b, "flowkv", num_prefill=2, num_decode=2,
                     routing="round_robin")
    sim.run(generate(wl, rps=0.2, seed=0), t_max=20_000)
    assert all(n.served_prefill + n.served_decode > 0
               for n in sim.nodes.values())


def test_hw_nodes_mixed_fleet_and_length_check(cfg8b):
    sim = ClusterSim(cfg8b, "flowkv", num_prefill=2, num_decode=2,
                     hw_nodes=(A100, L20, A100, H20))
    assert sim.nodes[1].hw is L20 and sim.nodes[3].hw is H20
    caps = sim.controller._capabilities()
    assert caps[0] == (1.0, pytest.approx(0.5), pytest.approx(80 / 96))
    with pytest.raises(ValueError, match="hw_nodes"):
        ClusterSim(cfg8b, "flowkv", num_prefill=1, num_decode=1,
                   hw_nodes=(A100,))


def test_generate_mixture_draws_from_both_specs():
    heavy = WorkloadSpec("h", 4096, 16)
    light = WorkloadSpec("l", 64, 256)
    reqs = generate_mixture([heavy, light], [0.5, 0.5], rps=1.0,
                            num_requests=60, seed=3)
    assert len(reqs) == 60
    lens = {r.prompt_len > 1000 for r in reqs}
    assert lens == {True, False}, "mixture never drew one of the specs"
    assert all(reqs[i].arrival_time <= reqs[i + 1].arrival_time
               for i in range(len(reqs) - 1))


def test_overload_scenario_gate_smoke():
    """Overload scenario: the admission gate fires for the load-aware
    policy and goodput/p95 beat the naive baseline (same gate CI's
    scenario-smoke job runs through benchmarks/scenarios.py --check)."""
    sc = get_scenario("overload")
    la = sc.run("load_aware")
    rr = sc.run("round_robin")
    assert la["rejected"] > 0
    assert rr["rejected"] == 0
    assert la["goodput"] >= rr["goodput"]
    assert la["p95_ttft_s"] <= rr["p95_ttft_s"]
    assert la["finished"] + la["rejected"] == la["offered"] == sc.num_requests


def test_scenario_registry_complete():
    assert set(SCENARIOS) == {"normal", "imbalance", "overload",
                              "heterogeneous", "failure", "multiturn",
                              "sharded_heterogeneous"}
    for name, sc in SCENARIOS.items():
        assert sc.name == name and sc.description
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("nope")


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,expect", [
    ("cpu", "cpu", "TPUv5e"),            # no accelerator: the modelled target
    ("tpu", "TPU v5 lite", "TPUv5e"),    # the chip's own profile
    ("tpu", "TPU v9 imaginary", None),   # a TPU with no profile raises
])
def test_local_hardware_by_device_kind(monkeypatch, platform, kind, expect):
    import jax

    from repro.sim import hardware
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(platform, kind)])
    if expect is None:
        with pytest.raises(ValueError, match="no hardware profile"):
            hardware.local_hardware()
    else:
        assert hardware.local_hardware().name == expect
