"""Multi-tier cluster runtime tests: N-tier simulator routing invariants,
two-tier backward compatibility, the live ClusterServer, and regressions for
the simulator accounting/hedging fixes."""
import numpy as np
import pytest

from repro.config import (ClusterTopology, PolicyConfig, ServingConfig,
                          SimConfig, TierSpec, get_topology,
                          two_tier_topology)
from repro.core import SystemState, make_policy
from repro.core.request import Job, RequestRecord
from repro.data.synthetic import RequestGenerator
from repro.serving.simulator import ClusterSimulator, EdgeCloudSimulator
from repro.serving.tiers import ClusterServer, build_cluster_engines


def _run_topology_sim(topology, policy="moa-off", n=150, rate=4.0, seed=0,
                      **kw):
    sim = ClusterSimulator(SimConfig(seed=seed), policy_name=policy,
                           topology=topology, **kw)
    for r in RequestGenerator(seed=seed, arrival_rate=rate).generate(n):
        sim.submit(r)
    sim.run()
    return sim


# ---------------------------------------------------------------------------
# topology plumbing
# ---------------------------------------------------------------------------


def test_topology_helpers():
    topo = get_topology("edge-regional-cloud")
    assert topo.names == ("edge", "regional", "cloud")
    assert [t.name for t in topo.local_tiers] == ["edge"]
    assert {t.name for t in topo.remote_tiers} == {"regional", "cloud"}
    assert topo.default_local.name == "edge"
    assert topo.default_remote.name == "cloud"  # max capability remote
    # fusion: most capable routed tier; all-local stays local
    assert topo.fusion_tier({"image": "cloud", "text": "edge"}) == "cloud"
    assert topo.fusion_tier({"image": "regional", "text": "edge"}) == "regional"
    assert topo.fusion_tier({"image": "edge", "text": "edge"}) == "edge"


def test_topology_rejects_duplicate_tier_names():
    t = TierSpec("edge", "qwen2-vl-2b", 1, 1e12, 1e9)
    with pytest.raises(ValueError):
        ClusterTopology("bad", (t, t))


def test_policy_multi_tier_splits_by_complexity():
    topo = get_topology("edge-regional-cloud")
    pol = make_policy("moa-off", PolicyConfig(adaptive_tau=False),
                      topology=topo)
    state = SystemState(edge_load=0.1, bandwidth_bps=3e8)
    from repro.core.request import Request

    req = Request(rid=0, arrival_s=0.0, modalities={})
    d = pol.decide(req, {"image": 0.95, "text": 0.05}, state)
    assert d.routes["text"] == "edge"  # easy stays local
    assert d.routes["image"] == "cloud"  # beyond the regional's capability
    d2 = pol.decide(req, {"image": 0.7}, state)
    # mid complexity: offloaded, but the regional tier is eligible
    assert d2.routes["image"] in ("regional", "cloud")


# ---------------------------------------------------------------------------
# N-tier simulator invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["edge-edge-cloud", "edge-regional-cloud"])
def test_three_tier_sim_routing_invariants(name):
    topo = get_topology(name)
    sim = _run_topology_sim(topo, n=150, rate=4.0)
    assert len(sim.outcomes) == 150
    declared = set(topo.names)
    for o in sim.outcomes:
        assert set(o.routes.values()) <= declared  # every modality routed
        assert o.served_tier in declared
    m = sim.metrics()
    # per-tier metrics sum to the totals
    assert sum(m[f"{t}_flops"] for t in topo.names) == pytest.approx(
        m["total_flops"])
    assert sum(m[f"{t}_mem_byte_s"] for t in topo.names) == pytest.approx(
        m["total_mem_byte_s"])
    # outcome-level attribution matches the aggregate
    assert sum(v for o in sim.outcomes for v in o.tier_flops.values()) == \
        pytest.approx(m["total_flops"])


def test_three_tier_uses_more_than_two_tiers():
    sim = _run_topology_sim(get_topology("edge-regional-cloud"),
                            n=250, rate=3.0)
    served = {o.served_tier for o in sim.outcomes}
    assert len(served) >= 3  # the middle tier genuinely takes traffic


def test_all_policies_run_on_three_tiers():
    topo = get_topology("edge-edge-cloud")
    for pol in ("moa-off", "cloud-only", "edge-only", "perllm",
                "moa-off-no-modality", "moa-off-no-collab"):
        sim = _run_topology_sim(topo, policy=pol, n=40, rate=2.0)
        assert len(sim.outcomes) == 40, pol
    # baselines anchor on the declared tiers
    sim_c = _run_topology_sim(topo, policy="cloud-only", n=20, rate=2.0)
    assert {o.served_tier for o in sim_c.outcomes} == {"cloud"}
    sim_e = _run_topology_sim(topo, policy="edge-only", n=20, rate=2.0)
    assert {o.served_tier for o in sim_e.outcomes} == {"edge"}


# ---------------------------------------------------------------------------
# two-tier backward compatibility
# ---------------------------------------------------------------------------

LEGACY_METRIC_KEYS = {
    "accuracy", "mean_latency_s", "p50_latency_s", "p95_latency_s",
    "p99_latency_s", "edge_flops", "cloud_flops", "total_flops",
    "edge_mem_byte_s", "cloud_mem_byte_s", "total_mem_byte_s",
    "edge_util", "cloud_util", "frac_edge", "hedged", "retries",
}


def test_two_tier_default_keeps_metric_keys_and_routes():
    sim = EdgeCloudSimulator(SimConfig(bandwidth_bps=300e6, seed=0),
                             policy_name="moa-off",
                             cloud_servers=1, edge_servers=1)
    for r in RequestGenerator(seed=0, arrival_rate=2.0).generate(100):
        sim.submit(r)
    sim.run()
    m = sim.metrics()
    assert LEGACY_METRIC_KEYS <= set(m)
    assert sim.topology.names == ("edge", "cloud")
    for o in sim.outcomes:
        assert set(o.routes.values()) <= {"edge", "cloud"}
        # legacy Outcome scalars still read through to the tier dicts
        assert o.edge_flops + o.cloud_flops == pytest.approx(
            sum(o.tier_flops.values()))
    assert m["edge_flops"] + m["cloud_flops"] == pytest.approx(
        m["total_flops"])
    assert 0.0 < m["frac_edge"] < 1.0


def test_two_tier_decisions_match_literal_eq5():
    """On the default topology the N-tier policy must reduce to Eq. 5."""
    from repro.core.policy import OffloadingPolicy, decide_modality
    from repro.core.request import Request

    pol = OffloadingPolicy(PolicyConfig(adaptive_tau=False))
    req = Request(rid=0, arrival_s=0.0, modalities={})
    rng = np.random.default_rng(0)
    for _ in range(200):
        state = SystemState(edge_load=rng.uniform(0, 1),
                            bandwidth_bps=rng.uniform(1e6, 1e9),
                            cloud_load=rng.uniform(0, 1))
        c = float(rng.uniform(0, 1))
        d = pol.decide(req, {"image": c}, state)
        assert d.routes["image"] == decide_modality(
            c, pol.taus["image"], state, pol.cfg)


# ---------------------------------------------------------------------------
# accounting + hedging regressions (simulator fixes)
# ---------------------------------------------------------------------------


def test_service_request_is_side_effect_free():
    sim = EdgeCloudSimulator(SimConfig(seed=0), cloud_servers=1,
                             edge_servers=1)
    req = RequestGenerator(seed=3, arrival_rate=1.0).generate(1)[0]
    decision = sim.scheduler.route(req)
    job = Job(request=req, decision=decision, fusion="cloud", tier="cloud",
              t_start=0.0, record=RequestRecord(rid=req.rid))
    before = {n: (st.flops, st.mem_byte_s) for n, st in sim.stations.items()}
    a = sim.backend._service_request(job)
    b = sim.backend._service_request(job)
    assert a == b  # deterministic
    after = {n: (st.flops, st.mem_byte_s) for n, st in sim.stations.items()}
    assert before == after  # no accounting side effects


def test_encode_charges_applied_once():
    """Partial-offload encode work lands on the routed tier exactly once,
    no matter how often the job's cost is (re)evaluated."""
    sim = EdgeCloudSimulator(SimConfig(seed=0), policy_name="moa-off",
                             cloud_servers=1, edge_servers=1)
    for r in RequestGenerator(seed=0, arrival_rate=2.0).generate(60):
        sim.submit(r)
    sim.run()
    assert sim.encode_flops.get("edge", 0.0) > 0  # partial offload happened
    # station counters == outcome service attribution + one encode charge
    for name, st in sim.stations.items():
        attributed = sum(o.tier_flops.get(name, 0.0) for o in sim.outcomes)
        assert st.flops == pytest.approx(
            attributed + sim.encode_flops.get(name, 0.0))


def test_hedge_skips_jobs_already_in_service():
    sim = EdgeCloudSimulator(SimConfig(seed=0), hedge_after_s=1.0,
                             cloud_servers=1, edge_servers=1)
    req = RequestGenerator(seed=1).generate(1)[0]
    job = Job(request=req, decision=sim.scheduler.route(req), fusion="edge",
              tier="edge", t_start=0.0, record=RequestRecord(rid=req.rid))
    sim.backend.start_service(0.0, sim.stations["edge"], job)
    assert job.in_service
    n_events = len(sim.events)

    class Ev:
        payload = {"job": job}
        t = 1.0

    sim.runtime._on_hedge_check(Ev())
    assert not job.hedged  # in-service job is left alone
    assert len(sim.events) == n_events


def test_hedged_straggler_produces_single_outcome():
    sim = EdgeCloudSimulator(SimConfig(seed=0), policy_name="edge-only",
                             hedge_after_s=0.5, cloud_servers=1,
                             edge_servers=1)
    n = 60
    for r in RequestGenerator(seed=0, arrival_rate=8.0).generate(n):
        sim.submit(r)
    sim.run()
    rids = [o.rid for o in sim.outcomes]
    assert len(rids) == len(set(rids)) == n  # no duplicated outcomes
    assert any(o.hedged for o in sim.outcomes)  # queued jobs were hedged


# ---------------------------------------------------------------------------
# live ClusterServer smoke (3 reduced-model engines)
# ---------------------------------------------------------------------------


def test_cluster_server_three_tiers_live():
    sv = ServingConfig(max_batch=2, max_seq=96)
    topo = get_topology("edge-regional-cloud")
    srv = ClusterServer(build_cluster_engines(topo, sv), topology=topo)
    rng = np.random.default_rng(0)
    from repro.data.synthetic import make_image

    for i, u in enumerate([0.05, 0.95, 0.5]):
        srv.submit(f"Describe {i}. " + "pad " * int(u * 60),
                   image=make_image(rng, u, 48, 48), max_new=4)
    res = srv.run()
    assert len(res) == 3
    for r in res:
        assert r.tier in topo.names
        assert set(r.routes.values()) <= set(topo.names)
        assert len(r.tokens) >= 1
    tiers = {r.rid: r.tier for r in res}
    assert tiers[0] == "edge"  # easy request stays local
    assert tiers[1] != "edge"  # complex image offloads


def test_cluster_server_requires_engine_per_tier():
    topo = two_tier_topology()
    with pytest.raises(ValueError):
        ClusterServer({"edge": None}, topology=topo)


# ---------------------------------------------------------------------------
# return-path modeling: embedding uplink + response downlink
# ---------------------------------------------------------------------------


def _split_request(decode_tokens=64):
    from repro.core.request import ModalityInput, Request

    # easy image (stays on edge) + hard text (goes to cloud): fusion is the
    # remote cloud, so the edge-encoded image's embeddings must ride the
    # cloud uplink
    return Request(rid=0, arrival_s=0.0, modalities={
        "image": ModalityInput("image", size_bytes=100_000, complexity=0.05,
                               meta={"h": 64, "w": 64}),
        "text": ModalityInput("text", size_bytes=128, complexity=0.95,
                              meta={"tokens": 32, "entities": 2,
                                    "sentences": 1}),
    }, decode_tokens=decode_tokens, slo_s=30.0)


def test_embeddings_ride_the_remote_fusion_uplink():
    from repro.serving import cost_model as cm

    sim = EdgeCloudSimulator(SimConfig(seed=0), cloud_servers=1,
                             edge_servers=1)
    sim.submit(_split_request())
    (out,) = sim.run()
    assert out.routes == {"image": "edge", "text": "cloud"}
    assert out.served_tier == "cloud"
    # uplink carries the text payload plus the compact image embeddings in
    # the fusion model's geometry — NOT the 100 kB raw image
    want = 128.0 + cm.embedding_bytes(sim.models["cloud"])
    assert out.transfer_bytes == pytest.approx(want)


def test_response_tokens_ride_the_downlink():
    import dataclasses as dc

    from repro.serving import cost_model as cm

    def run(downlink_bps):
        topo = two_tier_topology()
        topo = dc.replace(topo, tiers=tuple(
            dc.replace(t, downlink_bps=downlink_bps) if t.is_remote else t
            for t in topo.tiers))
        sim = ClusterSimulator(SimConfig(seed=0), topology=topo)
        sim.submit(_split_request(decode_tokens=64))
        (out,) = sim.run()
        return out, sim.topology.tier("cloud")

    fast, spec_fast = run(0.0)  # 0 -> symmetric with the uplink
    slow, spec_slow = run(64 * cm.RESPONSE_BYTES_PER_TOKEN)  # 8 s of payload
    want = (cm.downlink_seconds(64, spec_slow)
            - cm.downlink_seconds(64, spec_fast))
    assert want > 1.0  # the constriction is what we measure
    assert slow.latency_s - fast.latency_s == pytest.approx(want)
