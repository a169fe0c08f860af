"""Byzantine wire defense: checksummed frames and slot payloads detect
every injected corruption, the sequenced delivery guard makes event
streams exactly-once under drop/dup/reorder chaos, and the runtime
invariant auditor certifies that a faulted run left no residue —
duplicate outcomes, stuck stations, leaked slots or unconserved pages.

The fuzz tests are seeded and exhaustive-by-trial (no dependency); a
hypothesis twin widens the search when the optional dev dependency is
installed."""
import numpy as np
import pytest

from repro.serving.engine import MigrationError, SeqState, SlotPayload
from repro.serving.faults import FaultEvent, FaultPlan, WireChaos
from repro.serving.transport import (DeliveryGuard, LocalTransport,
                                     TransportError, msg_from_bytes,
                                     msg_to_bytes)

# ---------------------------------------------------------------------------
# frame integrity: any flip in a checksummed region raises, never crashes
# ---------------------------------------------------------------------------


def _frame() -> bytes:
    return msg_to_bytes("events", (7, [("token", 3, 11, 0.25),
                                       ("admit", 4, 0.5)]))


def test_frame_roundtrip():
    kind, payload = msg_from_bytes(_frame())
    assert kind == "events"
    assert payload[0] == 7


def test_frame_flips_always_detected():
    """500 seeded random 1–4 byte flips anywhere in the frame: every one
    raises TransportError (100% detection), none crashes."""
    frame = _frame()
    rng = np.random.default_rng(0)
    for _ in range(500):
        corrupt = bytearray(frame)
        for _ in range(int(rng.integers(1, 5))):
            pos = int(rng.integers(len(corrupt)))
            corrupt[pos] ^= int(rng.integers(1, 256))
        with pytest.raises(TransportError):
            msg_from_bytes(bytes(corrupt))


def test_frame_truncations_always_detected():
    frame = _frame()
    for n in range(len(frame)):
        with pytest.raises(TransportError):
            msg_from_bytes(frame[:n])


def test_frame_size_cap_blocks_giant_allocation():
    frame = _frame()
    with pytest.raises(TransportError, match="oversized"):
        msg_from_bytes(frame, max_frame_bytes=len(frame) - 1)
    # at the cap it parses fine
    assert msg_from_bytes(frame, max_frame_bytes=len(frame))[0] == "events"


def test_frame_rejects_wrong_version_and_magic():
    frame = bytearray(_frame())
    with pytest.raises(TransportError, match="magic"):
        msg_from_bytes(b"XXXX" + bytes(frame[4:]))
    bad_ver = bytearray(frame)
    bad_ver[4] ^= 0xFF  # little-endian version low byte
    with pytest.raises(TransportError, match="version|checksum"):
        msg_from_bytes(bytes(bad_ver))


def test_frame_flips_hypothesis():
    pytest.importorskip(
        "hypothesis", reason="optional dev dependency (requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    frame = _frame()

    @given(pos=st.integers(0, len(frame) - 1), mask=st.integers(1, 255))
    @settings(max_examples=200, deadline=None)
    def check(pos, mask):
        corrupt = bytearray(frame)
        corrupt[pos] ^= mask
        with pytest.raises(TransportError):
            msg_from_bytes(bytes(corrupt))

    check()


# ---------------------------------------------------------------------------
# slot payload integrity
# ---------------------------------------------------------------------------


def _payload() -> SlotPayload:
    rng = np.random.default_rng(1)
    return SlotPayload(
        version=2, model="toy", family="dense", max_seq=64,
        seq=SeqState(rid=5, prompt_len=8, generated=[9, 10], max_new=4,
                     done=False, t_submit=0.0, t_first_token=None,
                     t_done=None),
        position=10, key=np.asarray([3, 4], np.uint32),
        leaves={"kv/0": rng.standard_normal((2, 16, 4)).astype(np.float32),
                "kv/1": rng.standard_normal((2, 16, 4)).astype(np.float32)})


def test_slot_payload_roundtrip_with_checksums():
    p = _payload()
    q = SlotPayload.from_bytes(p.to_bytes())
    assert q.seq.rid == 5 and q.position == 10
    for name in p.leaves:
        np.testing.assert_array_equal(p.leaves[name], q.leaves[name])


def test_slot_payload_flips_always_detected():
    """Seeded random flips anywhere in the wire — header or any raw
    buffer — always raise MigrationError before any state is built."""
    wire = _payload().to_bytes()
    rng = np.random.default_rng(2)
    for _ in range(500):
        corrupt = bytearray(wire)
        for _ in range(int(rng.integers(1, 5))):
            pos = int(rng.integers(len(corrupt)))
            corrupt[pos] ^= int(rng.integers(1, 256))
        with pytest.raises(MigrationError):
            SlotPayload.from_bytes(bytes(corrupt))


def test_slot_payload_truncations_always_detected():
    wire = _payload().to_bytes()
    for n in range(0, len(wire), 7):
        with pytest.raises(MigrationError):
            SlotPayload.from_bytes(wire[:n])


def test_slot_payload_flips_hypothesis():
    pytest.importorskip(
        "hypothesis", reason="optional dev dependency (requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    wire = _payload().to_bytes()

    @given(pos=st.integers(0, len(wire) - 1), mask=st.integers(1, 255))
    @settings(max_examples=200, deadline=None)
    def check(pos, mask):
        corrupt = bytearray(wire)
        corrupt[pos] ^= mask
        with pytest.raises(MigrationError):
            SlotPayload.from_bytes(bytes(corrupt))

    check()


# ---------------------------------------------------------------------------
# delivery guard: exactly-once over dup / drop / reorder
# ---------------------------------------------------------------------------


def test_guard_duplicates_suppressed():
    g = DeliveryGuard("events:t/0")
    g.receive(1, "ev", "a")
    g.receive(1, "ev", "a")
    g.receive(2, "ev", "b")
    g.receive(2, "ev", "b")
    assert g.drain() == [("ev", "a"), ("ev", "b")]
    assert g.stats.get("dups_suppressed") == 2
    assert g.audit("t/0") == []


def test_guard_reorder_restored():
    g = DeliveryGuard("events:t/0")
    g.receive(2, "ev", "b")
    g.receive(1, "ev", "a")
    g.receive(3, "ev", "c")
    assert g.drain() == [("ev", "a"), ("ev", "b"), ("ev", "c")]
    assert g.audit("t/0") == []


def test_guard_gap_resyncs_from_outbox():
    outbox = [(1, "ev", "a"), (2, "ev", "b"), (3, "ev", "c")]
    replayed = []

    def resync(last_seq):
        replayed.append(last_seq)
        for seq, kind, payload in outbox:
            if seq > last_seq:
                g.redeliver(seq, kind, payload)

    g = DeliveryGuard("events:t/0", resync=resync)
    g.receive(1, "ev", "a")
    g.receive(3, "ev", "c")  # 2 was dropped on the wire
    g.heal()
    assert replayed == [1]
    assert g.drain() == [("ev", "a"), ("ev", "b"), ("ev", "c")]
    assert g.stats.get("gaps_detected") == 1
    assert g.stats.get("resyncs") == 1
    assert g.audit("t/0") == []


def test_guard_dropped_tail_detected_via_high_water():
    """A dropped FINAL frame has no successor to reveal the gap; the
    sender's advertised high-water mark must still trigger the resync."""
    sent = []

    def resync(last_seq):
        for seq, kind, payload in sent:
            if seq > last_seq:
                g.redeliver(seq, kind, payload)

    g = DeliveryGuard("events:t/0", resync=resync)
    sent.append((1, "fin", "x"))
    g.expected = 1  # sender advertised seq 1; the frame itself vanished
    g.heal()
    assert g.drain() == [("fin", "x")]
    assert g.audit("t/0") == []


def test_guard_abandons_unhealable_gap_for_liveness():
    g = DeliveryGuard("events:t/0", resync=lambda last: None,
                      resync_patience=2)
    g.receive(1, "ev", "a")
    g.receive(4, "ev", "d")  # 2 and 3 are gone forever (sender died)
    for _ in range(5):
        g.heal()
    assert g.drain() == [("ev", "a"), ("ev", "d")]
    assert g.stats.get("gaps_abandoned") == 1
    assert g.audit("t/0") == []  # ledger closed: liveness preserved


def test_guard_chaos_drop_dup_reorder_end_clean():
    """A seeded byzantine schedule on the wire side of the guard: whatever
    mix of drops/dups/reorders fires, the drained stream is exactly the
    sent stream, in order, and the ledger closes clean."""
    plan = FaultPlan([FaultEvent("msg_drop", "*", magnitude=0.2),
                      FaultEvent("msg_dup", "*", magnitude=0.3),
                      FaultEvent("msg_reorder", "*", magnitude=0.2)],
                     wire_seed=5)
    chaos = WireChaos(plan)
    outbox = []

    def resync(last_seq):
        for seq, kind, payload in outbox:
            if seq > last_seq:
                g.redeliver(seq, kind, payload)

    g = DeliveryGuard("events:t/0", chaos=chaos, stats=chaos.stats,
                      resync=resync, resync_patience=0)
    n = 200
    for i in range(1, n + 1):
        outbox.append((i, "ev", i))
        g.expected = i
        g.receive(i, "ev", i)
        g.heal()
    g.heal()
    got = [payload for _, payload in g.drain()]
    assert got == list(range(1, n + 1))
    assert g.audit("t/0") == []
    # the schedule actually fired
    assert chaos.stats.get("msgs_dropped", 0) > 0
    assert chaos.stats.get("msgs_duped", 0) > 0
    assert chaos.stats.get("msgs_reordered", 0) > 0
    assert chaos.stats.get("dups_suppressed", 0) > 0


# ---------------------------------------------------------------------------
# per-family live engines behind a chaotic local transport
# ---------------------------------------------------------------------------

from test_migration import FAMILIES, make_engine  # noqa: E402


def _drive(transport, jobs, chaos_events):
    """Submit jobs and poll to completion; returns {rid: generated}."""
    events = []
    transport.wire_hooks(
        lambda rid, t_admit, t: events.append(("admit", rid)),
        lambda rid, tok, t: events.append(("token", rid, tok)),
        lambda rid, k, c, s: None, lambda rid, sid: None)
    if chaos_events is not None:
        transport.arm_delivery(chaos_events, chaos_events.stats,
                               lambda: 0.0, "events:edge/0")
    for rid, toks, max_new in jobs:
        transport.submit(rid, toks, max_new, {}, None, None)
    done = {}
    for _ in range(10_000):
        fins, active, _ = transport.poll()
        for f in fins:
            done[f.rid] = list(f.generated)
        if not active and len(done) == len(jobs):
            break
    return done, events


@pytest.mark.slow
@pytest.mark.parametrize("family", FAMILIES)
def test_byzantine_event_stream_token_identical(family, family_model):
    """drop/dup/reorder chaos on a replica's event stream never changes
    the delivered tokens (temp=0) for ANY model family — the guard heals
    everything within the poll, and its ledger closes clean."""
    cfg, params = family_model(family)
    jobs = [(rid, (np.arange(6 + 3 * rid) % 300 + 4).astype(np.int32), 8)
            for rid in range(3)]
    honest, _ = _drive(LocalTransport(make_engine(cfg, params)), jobs, None)

    plan = FaultPlan([FaultEvent("msg_drop", "*", magnitude=0.25),
                      FaultEvent("msg_dup", "*", magnitude=0.25),
                      FaultEvent("msg_reorder", "*", magnitude=0.25)],
                     wire_seed=7)
    chaos = WireChaos(plan)
    tr = LocalTransport(make_engine(cfg, params))
    chaotic, events = _drive(tr, jobs, chaos)

    assert chaotic == honest  # token-identical despite the storm
    assert tr._guard.audit("edge/0") == []
    assert chaos.stats.get("dups_suppressed", 0) > 0
    # every delivered token arrived exactly once, in order
    per_rid = {}
    for ev in events:
        if ev[0] == "token":
            per_rid.setdefault(ev[1], []).append(ev[2])
    for rid, toks in honest.items():
        assert per_rid[rid] == toks


# ---------------------------------------------------------------------------
# the invariant auditor itself
# ---------------------------------------------------------------------------


class _Stub:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _stub_runtime(outcomes, records, links=None, wire=None, residue=()):
    return _Stub(outcomes=outcomes, records=records, links=links or {},
                 wire_stats=wire or {},
                 backend=_Stub(audit_residue=lambda: list(residue)))


def test_auditor_flags_duplicate_and_missing_outcomes():
    from repro.serving.audit import InvariantAuditor

    rec_done = _Stub(done=True)
    rec_open = _Stub(done=False)
    rt = _stub_runtime(
        outcomes=[_Stub(rid=1), _Stub(rid=1)],  # double-served
        records={1: rec_done, 2: rec_open})      # 2 never finished
    v = InvariantAuditor(rt).final_check()
    assert not v["clean"]
    text = " ".join(v["violations"])
    assert "2 terminal Outcomes" in text
    assert "no terminal Outcome" in text


def test_auditor_flags_stuck_station_and_undetected_corruption():
    from repro.serving.audit import InvariantAuditor

    rt = _stub_runtime(
        outcomes=[_Stub(rid=1)], records={1: _Stub(done=True)},
        links={"wan:edge": _Stub(busy=1, queue=[object()])},
        wire={"corrupt_undetected": 2},
        residue=["edge: slot 0 still busy (rid 9)"])
    v = InvariantAuditor(rt).final_check()
    assert not v["clean"]
    text = " ".join(v["violations"])
    assert "busy" in text and "undetected" in text and "slot 0" in text
    assert v["wire"]["corrupt_undetected"] == 2


def test_auditor_clean_on_consistent_state():
    from repro.serving.audit import InvariantAuditor

    rt = _stub_runtime(outcomes=[_Stub(rid=1)],
                       records={1: _Stub(done=True)},
                       links={"wan:edge": _Stub(busy=0, queue=[])})
    v = InvariantAuditor(rt).final_check()
    assert v["clean"] and v["violations"] == []
    assert v["requests"] == v["outcomes"] == 1


# ---------------------------------------------------------------------------
# end to end: byzantine storms through the full live control plane
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_live_cluster_byzantine_storm_audits_clean():
    """The full live control plane under event-stream chaos with the
    auditor on: identical served tokens to the honest run, a clean
    verdict, and the wire counters prove faults actually fired."""
    from conftest import make_twin_edge_server

    plan = FaultPlan([FaultEvent("msg_drop", "*", magnitude=0.2),
                      FaultEvent("msg_dup", "*", magnitude=0.3),
                      FaultEvent("msg_reorder", "*", magnitude=0.2)],
                     wire_seed=11)
    runs = {}
    for mode, fp in (("honest", None), ("byzantine", plan)):
        server = make_twin_edge_server(fault_plan=fp, audit=True)
        for i in range(3):
            server.submit(f"describe scene {i} please now. " * 2,
                          max_new=8, complexity={"text": 0.05})
        results = server.run(timeout_s=120.0)
        runs[mode] = sorted((r.rid, tuple(r.tokens)) for r in results)
        verdict = server.runtime.auditor.last
        assert verdict["clean"], verdict["violations"]
        if fp is not None:
            ws = server.runtime.wire_stats
            assert ws.get("dups_suppressed", 0) > 0
            assert ws.get("msgs_dropped", 0) > 0
            assert ws.get("corrupt_undetected", 0) == 0
    assert runs["byzantine"] == runs["honest"]


@pytest.mark.slow
def test_live_corrupt_migration_detected_and_recovered():
    """Every migration wire corrupted (p=1): the payload CRC rejects the
    inject, the clone re-prefills (recovered — the request completes with
    correct tokens), corrupt_detected counts it, nothing slips through,
    and the auditor signs off."""
    from conftest import make_twin_edge_server

    plan = FaultPlan([FaultEvent("corrupt", "*", magnitude=1.0)],
                     wire_seed=3)
    server = make_twin_edge_server(hedge_after_s=0.05, migrate=True,
                                   fault_plan=plan, audit=True)
    req = server.build_request("please describe this Scene in depth. " * 3,
                               max_new=100, complexity={"text": 0.05})
    server.submit_request(req)
    (res,) = server.run(timeout_s=120.0)
    ws = server.runtime.wire_stats
    assert ws.get("corrupt_injected", 0) >= 1
    assert ws.get("corrupt_detected", 0) >= 1
    assert ws.get("corrupt_undetected", 0) == 0
    assert not res.failed and not res.migrated  # recovered via re-prefill
    assert len(res.tokens) > 0
    verdict = server.runtime.auditor.last
    assert verdict["clean"], verdict["violations"]


def test_wire_chaos_determinism():
    """Two WireChaos instances over the same plan make identical decisions
    per link regardless of interleaving across links."""
    plan = FaultPlan.byzantine_storm(seed=9, corrupt=0.4, dup=0.3,
                                     drop=0.2, reorder=0.1)
    a, b = WireChaos(plan), WireChaos(plan)
    links = ["events:edge/0", "events:cloud/0", "migrate:edge1"]
    seq_a = [(k, ln, a.decide(k, ln, 0.0))
             for ln in links for k in ("corrupt", "msg_drop", "msg_dup")
             for _ in range(20)]
    # b interleaves the SAME per-link queries in a different global order
    seq_b = {}
    for k in ("corrupt", "msg_drop", "msg_dup"):
        for ln in links:
            seq_b[(k, ln)] = [b.decide(k, ln, 123.0) for _ in range(20)]
    per_link = {}
    for k, ln, v in seq_a:
        per_link.setdefault((k, ln), []).append(v)
    assert per_link == seq_b  # t differs, windows are infinite: same fate
    assert any(v for vs in seq_b.values() for v in vs)  # storm is real
