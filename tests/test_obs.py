"""Host spans (``repro.obs``) and the lifecycle record's times.

Off, a span is one shared no-op context; on, spans nest with their
parents, rids and fields, and a CPU serve puts them at the layer
boundaries only: one block, block read and emit span per fused block,
nothing under the token loop. Every record of a serve is timed
``enqueue <= admit <= serve <= complete`` with its trace unchanged, and a
harness run at its CPU size logs the same work in its wrappers as the
engine's ``moa.engine.block``/``moa.engine.prefill`` span fields.
"""
import re
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.config import ServingConfig, two_tier_topology
from repro.data.synthetic import make_image
from repro.launch import serve
from repro.launch.serve import paged_live_share, span_table
from repro.models import build_model
from repro.serving import engine as engine_mod
from repro.serving.engine import TierEngine
from repro.serving.tiers import ClusterServer, build_cluster_engines

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def spans_on():
    obs.take()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.take()


def test_off_returns_the_shared_noop_and_records_nothing():
    obs.disable()
    obs.take()
    a = obs.span("moa.a")
    b = obs.span("moa.b", rid=3, shape=(4, 4))
    assert a is b
    with a:
        with b:
            pass
    assert obs.take() == []


def test_on_spans_nest_with_parents_rids_fields_and_self_time(spans_on):
    with obs.span("moa.outer", rid=7, tier="edge"):
        time.sleep(0.01)
        for _ in range(2):
            with obs.span("moa.inner"):
                time.sleep(0.005)
    spans = obs.take()
    assert [s.name for s in spans] == ["moa.inner", "moa.inner", "moa.outer"]
    outer = spans[-1]
    assert outer.parent is None and outer.rid == 7
    assert outer.fields == {"tier": "edge"}
    for s in spans[:2]:
        assert s.parent == "moa.outer" and s.rid is None
        assert outer.t0 <= s.t0 <= s.t1 <= outer.t1
    rows = {n: (c, tot, own) for n, c, tot, own in span_table(spans)}
    inner_total = sum(s.t1 - s.t0 for s in spans[:2])
    assert rows["moa.inner"] == (2, inner_total, inner_total)
    c, tot, own = rows["moa.outer"]
    assert c == 1 and tot == outer.t1 - outer.t0
    assert own == pytest.approx(tot - inner_total, abs=1e-12)
    assert own >= 0.009
    assert obs.take() == []


def test_an_array_view_field_is_kept_as_its_own_copy(spans_on):
    lengths = np.arange(64, dtype=np.int32)
    with obs.span("moa.engine.prefill", lengths=lengths[:3], bucket=16):
        pass
    (s,) = obs.take()
    kept = s.fields["lengths"]
    assert kept.base is None and kept.tolist() == [0, 1, 2]
    assert s.fields["bucket"] == 16


def test_serve_trace_dir_profiles_and_prints_the_span_table(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "serve", "--requests", "2", "--max-new", "4", "--trace-dir",
        str(tmp_path)])
    serve.main()
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("queue wait (enqueue to admission): "))
    p50, p95, n = re.fullmatch(
        r"queue wait \(enqueue to admission\): p50 ([0-9.]+) ms, "
        r"p95 ([0-9.]+) ms over (\d+) requests", line).groups()
    assert 0.0 <= float(p50) <= float(p95) and int(n) == 2
    table = out.split("spans (profile in", 1)[1]
    for name in ("moa.runtime.advance", "moa.scheduler.score.read",
                 "moa.engine.block", "moa.engine.prefill"):
        assert f"  {name} " in table, name
    live, grid, blocks = map(int, re.search(
        r"paged live share = [0-9.]+ \((\d+) of (\d+) page steps computed "
        r"over (\d+) blocks\)", out).groups())
    assert 0 < live <= grid and blocks > 0
    assert list(tmp_path.rglob("*.xplane.pb"))
    assert obs.span("moa.after") is obs.span("moa.after2")  # off again
    assert obs.take() == []


def test_block_span_counts_the_paged_kernels_pages(family_model,
                                                   monkeypatch):
    """A paged block's ``pages_grid`` is k x rows x pages of its bucket
    and ``pages_live`` the pages each row's kernel steps compute, counted
    here by hand from the span's positions, budgets, k and teff. With
    spans off the counts are never computed."""
    cfg, params = family_model("dense")
    page, max_seq = 32, 256
    sv = ServingConfig(max_batch=4, max_seq=max_seq, paged=True,
                       kv_page_size=page, fused_steps=8)
    eng = TierEngine(build_model(cfg), params, sv, eos_id=-1)
    rng = np.random.default_rng(0)
    # a one-page row, a row ending mid-block, a three-page row, an empty slot
    for rid, (n, max_new) in enumerate([(5, 40), (40, 3), (70, 40)]):
        eng.submit(rid, rng.integers(4, 200, size=n).astype(np.int32),
                   max_new=max_new)
    calls = []
    counted = engine_mod.block_pages
    monkeypatch.setattr(engine_mod, "block_pages",
                        lambda *a: calls.append(a) or counted(*a))
    obs.take()
    obs.enable()
    try:
        eng.step()
    finally:
        obs.disable()
    (blk,) = [s for s in obs.take() if s.name == "moa.engine.block"]
    f = blk.fields
    positions, budgets, k, teff = (f["positions"], f["budgets"], f["k"],
                                   f["teff"])
    assert positions.tolist() == [5, 40, 70, 0]
    assert budgets.tolist() == [39, 2, 39, 0] and k == 8 and teff == 96
    assert f["pages_grid"] == 8 * 4 * 3
    live = 0
    for j in range(k):
        for pos, budget in zip(positions.tolist(), budgets.tolist()):
            live += (pos + min(j, budget)) // page + 1 if budget else 1
    # rows of 1, 2 and 3 pages over 8 steps, the empty slot 1 a step
    assert f["pages_live"] == live == 8 * (1 + 2 + 3 + 1)
    assert paged_live_share([blk]) == (live, 96, 1)
    assert len(calls) == 1

    eng.step()  # spans off: no counts
    assert len(calls) == 1 and obs.take() == []


# -- a CPU serve through the cluster server ------------------------------------


@pytest.fixture(scope="module")
def server():
    topo = two_tier_topology()
    sv = ServingConfig(max_batch=2, max_seq=64)
    srv = ClusterServer(build_cluster_engines(topo, sv), topology=topo)
    _serve(srv, seed=99)  # compile every shape the runs below use
    return srv


def _serve(srv, seed):
    rng = np.random.default_rng(seed)
    rids = []
    for i in range(4):
        req = srv.build_request(
            f"Describe scene {i}. " + "and explain it. " * (i + 1),
            image=make_image(rng, 0.2 * (i + 1), 48, 48), max_new=12)
        srv.submit_request(req)
        rids.append(req.rid)
    srv.run()
    return [srv.runtime.records[r] for r in rids]


def _check_records(records):
    for rec in records:
        states = [s for s, _ in rec.trace()]
        assert rec.trace() == tuple(rec.events)
        assert len(rec.times) == len(rec.events)
        assert "admit" not in states  # admission is a time, not an event
        assert states[:2] == ["arrival", "routed"]
        assert states[-3:] == ["enqueue", "serve", "complete"]
        t_enq, t_serve = rec.time_of("enqueue"), rec.time_of("serve")
        t_done = rec.time_of("complete")
        assert rec.time_of("arrival") <= t_enq <= rec.t_admit <= t_serve
        assert t_serve <= t_done
        assert rec.queue_s() == rec.t_admit - t_enq >= 0.0


def test_serve_with_spans_off_records_none_and_makes_no_annotation(
        server, monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    obs.disable()
    obs.take()
    _check_records(_serve(server, seed=1))
    assert made == []
    assert obs.take() == []


def test_serve_spans_sit_at_layer_boundaries_only(server, spans_on):
    engines = server.engines.values()
    steps0 = sum(e.steps for e in engines)
    tokens0 = sum(e.decode_tokens for e in engines)
    records = _serve(server, seed=2)
    spans = obs.take()
    _check_records(records)
    blocks = sum(e.steps for e in engines) - steps0
    tokens = sum(e.decode_tokens for e in engines) - tokens0
    count = {}
    for s in spans:
        count[s.name] = count.get(s.name, 0) + 1
    assert blocks > 0 and tokens > 2 * blocks
    for name in ("moa.engine.block", "moa.engine.block.read",
                 "moa.engine.emit"):
        assert count[name] == blocks, name
    assert count["moa.engine.prefill"] == count["moa.engine.prefill.read"]
    assert count["moa.runtime.arrival"] == count["moa.scheduler.score"] == 4
    for child in ("prepare", "stats", "features", "read"):
        assert count[f"moa.scheduler.score.{child}"] == 4
    # nothing runs under a span inside the token loop or a device read
    assert not [s for s in spans if s.parent in (
        "moa.engine.emit", "moa.engine.block.read",
        "moa.engine.prefill.read", "moa.runtime.doze")]
    parents = {s.name: s.parent for s in spans}
    assert parents["moa.engine.block"] == "moa.engine.step"
    assert parents["moa.engine.prefill"] == "moa.engine.admit"
    assert parents["moa.engine.step"] == "moa.runtime.advance"
    assert parents["moa.scheduler.score"] == "moa.runtime.arrival"
    assert parents["moa.scheduler.score.stats"] == "moa.scheduler.score"
    tiers = {s.fields["tier"] for s in spans
             if s.name in ("moa.engine.block", "moa.engine.prefill")}
    assert tiers <= {"edge", "cloud"} and tiers
    assert all(s.rid is not None for s in spans
               if s.name in ("moa.runtime.arrival", "moa.scheduler.score"))


# -- the benchmark harness's work log against the span fields ------------------


def test_span_fields_match_the_harness_work_log(spans_on):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness

    seed, seconds = 2 ** 33 + 13, 2.0
    cell = harness.load_cell("pair.vqa-poisson")
    harness.shrink(cell)
    items, images = harness.schedule(cell, seed, seconds)
    obs.disable()
    st = harness.set_up(cell, seed, items, images)
    work: list = []
    harness.instrument(st.engines, st.server, work)
    obs.enable()
    out = harness.drive(cell, st, items, images, seconds, False)
    spans = obs.take()
    assert any(r.done for r in out.rows)

    blocks = [s for s in spans if s.name == "moa.engine.block"]
    decodes = [w for w in work if w[0] == "decode"]
    assert len(blocks) == len(decodes) > 0
    for s, (_, t, tier, positions, budgets, k) in zip(blocks, decodes):
        assert s.t0 <= t <= s.t1
        assert s.fields["tier"] == tier and s.fields["k"] == k
        assert np.array_equal(s.fields["positions"], positions)
        assert np.array_equal(s.fields["budgets"], budgets)
    prefills = [s for s in spans if s.name == "moa.engine.prefill"]
    logged = [w for w in work if w[0] == "prefill"]
    assert len(prefills) == len(logged) > 0
    for s, (_, t, tier, lengths, patches) in zip(prefills, logged):
        assert s.t0 <= t <= s.t1
        assert s.fields["tier"] == tier and s.fields["patches"] == patches
        assert np.array_equal(s.fields["lengths"], lengths)
    rt = st.server.runtime
    waits = [rt.records[r.rid].queue_s() for r in out.rows if r.done]
    assert waits and all(w is not None and w >= 0.0 for w in waits)
