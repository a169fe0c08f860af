"""CPU tests of ``bench/spans.py``: its three quantities on hand-made
spans and records, nothing read from a program without spans or times,
idle gaps put to the innermost span of either family, the recorded chip
trace's busy time, ops and programs unchanged by the program's spans, and
one run of the script at the CPU tests' sizes."""
import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spans as S  # noqa: E402
from bench import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _span(name, t0, t1, parent=None):
    return SimpleNamespace(name=name, t0=t0, t1=t1, parent=parent)


def _spans():
    """Two fused blocks in a 1 s window, one begun in the profiler's
    stretch [0.4, 0.6), and a doze."""
    return [_span("moa.engine.block", 0.10, 0.30, "moa.engine.step"),
            _span("moa.engine.block.read", 0.15, 0.30, "moa.engine.block"),
            _span("moa.engine.block", 0.45, 0.55, "moa.engine.step"),
            _span("moa.engine.block.read", 0.50, 0.55, "moa.engine.block"),
            _span("moa.engine.block", 0.70, 0.90, "moa.engine.step"),
            _span("moa.engine.block.read", 0.72, 0.85, "moa.engine.block"),
            _span("moa.scheduler.score.read", 0.55, 0.65,
                  "moa.scheduler.score"),
            _span("moa.runtime.doze", 0.92, 1.00, "moa.runtime.advance")]


def test_host_ms_per_block_leaves_out_reads_dozes_and_the_profiler():
    pcs = S.pieces(0.0, 1.0, (0.4, 0.6))
    assert pcs == [(0.0, 0.4), (0.6, 1.0)]
    # 0.8 s outside the stretch; waits there: 0.15 + 0.05 (score.read
    # after 0.6) + 0.13 + 0.08; blocks begun there: 2
    want = (0.8 - (0.15 + 0.05 + 0.13 + 0.08)) / 2 * 1e3
    assert S.host_ms_per_block(_spans(), pcs) == pytest.approx(want)
    whole = S.pieces(0.0, 1.0, (0.0, 0.0))
    want = (1.0 - (0.15 + 0.05 + 0.13 + 0.10 + 0.08)) / 3 * 1e3
    assert S.host_ms_per_block(_spans(), whole) == pytest.approx(want)


def test_span_seconds_count_total_and_self_time_inside_the_pieces():
    rows = S.span_seconds(_spans(), S.pieces(0.0, 1.0, (0.4, 0.6)))
    assert rows["moa.engine.block"][:2] == [2, pytest.approx(0.4)]
    assert rows["moa.engine.block"][2] == pytest.approx(0.4 - 0.28)
    assert rows["moa.engine.block.read"][1] == pytest.approx(0.28)


class _Rec:
    """A lifecycle record with times, as the program keeps it."""

    def __init__(self, t_enq, t_admit):
        self.t_enq, self.t_admit = t_enq, t_admit

    def time_of(self, state):
        return self.t_enq if state == "enqueue" else None

    def queue_s(self):
        if self.t_enq is None or self.t_admit is None:
            return None
        return self.t_admit - self.t_enq


def _rows(n):
    return [SimpleNamespace(rid=i, due=float(i)) for i in range(n)]


def test_queue_wait_p95_ranks_an_unadmitted_request_last():
    recs = {i: _Rec(i + 0.1, i + 0.1 + 0.01 * i) for i in range(20)}
    assert S.queue_wait_p95_ms(_rows(20), recs, 100.0) == pytest.approx(
        0.01 * 18 * 1e3)
    recs[3] = _Rec(3.2, None)  # never admitted: waited 96.8 s by the end
    recs[4] = _Rec(None, None)  # never enqueued: from its due time
    assert S.queue_wait_p95_ms(_rows(20), recs, 100.0) == pytest.approx(
        96.8 * 1e3)


def test_readers_find_nothing_in_a_program_without_spans_or_times():
    parent = {i: SimpleNamespace(events=[("enqueue", "edge")])
              for i in range(4)}
    assert S.queue_wait_p95_ms(_rows(4), parent, 10.0) is None
    assert S.queue_wait_p95_ms([], {}, 10.0) is None
    pcs = S.pieces(0.0, 1.0, (0.4, 0.6))
    assert S.host_ms_per_block([], pcs) is None
    assert S.host_ms_per_block(
        [s for s in _spans() if s.name != "moa.engine.block"], pcs) is None
    assert S.moa_idle_share({}) is None


def _events(host):
    ops = [("%fusion.1 = bf16[8] fusion(x)", 1.0, 0.2),
           ("%fusion.1 = bf16[8] fusion(x)", 1.6, 0.4)]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops}},
            "host": [("bench.window", 1.0, 1.0)] + host}


def test_idle_gaps_go_to_the_innermost_span_of_either_family():
    harness_spans = [("bench.engine.step", 1.0, 0.9)]
    program = [("moa.engine.step", 1.05, 0.8),
               ("moa.engine.block", 1.1, 0.6),
               ("moa.engine.block.read", 1.25, 0.1),
               ("moa.engine.emit", 1.5, 0.2)]
    red = trace.reduce_events(_events(harness_spans + program))
    # gaps: 1.2-1.6 (mid 1.4: block, the read ended at 1.35)
    assert red["gaps"] == {"moa.engine.block": pytest.approx(0.4)}
    red = trace.reduce_events(_events(harness_spans + program[:3] + [
        ("moa.engine.block.read", 1.3, 0.2)]))
    assert red["gaps"] == {"moa.engine.block.read": pytest.approx(0.4)}
    assert S.moa_idle_share(red["gaps"]) == pytest.approx(1.0)
    red = trace.reduce_events(_events(harness_spans))
    assert red["gaps"] == {"bench.engine.step": pytest.approx(0.4)}
    assert S.moa_idle_share(red["gaps"]) == 0.0


def test_program_spans_leave_the_recorded_trace_busy_ops_and_programs():
    ev = json.loads((DATA / "trace_v5e_excerpt.json").read_text())
    before = trace.reduce_events(ev)
    # the program's spans inside the harness's, as they run: a step
    # inside ``bench.engine.step`` that covers the whole window
    (_, s0, d0), = [h for h in ev["host"] if h[0] == "bench.engine.step"]
    lo, hi = ev["window"]
    assert s0 < lo and hi < s0 + d0
    with_program = dict(ev, host=ev["host"] + [
        ["moa.engine.step", s0 + 1e-4, d0 - 2e-4],
        ["moa.engine.block.read", lo + 0.005, 0.01]])
    after = trace.reduce_events(with_program)
    for key in ("window_s", "busy_s", "planes", "ops", "modules"):
        assert after[key] == before[key], key
    assert sum(after["gaps"].values()) == pytest.approx(
        sum(before["gaps"].values()))
    assert S.moa_idle_share(after["gaps"]) == pytest.approx(1.0)


def test_on_cost_pairs_each_seed_on_over_off():
    res = [{"seed": 1, "spans": "off", "traced": False,
            "metrics": {"latency_p95_s": 2.0, "queue_wait_p95_ms": 5.0}},
           {"seed": 1, "spans": "on", "traced": False,
            "metrics": {"latency_p95_s": 2.2, "queue_wait_p95_ms": 6.0,
                        "host_ms_per_block": 3.0}},
           {"seed": 2, "spans": "on", "traced": False,
            "metrics": {"latency_p95_s": 1.0}},
           {"seed": 2, "spans": "off", "traced": False,
            "metrics": {"latency_p95_s": 1.0}},
           {"seed": 3, "spans": "on", "traced": True,
            "metrics": {"latency_p95_s": 9.0}}]
    out = S.on_cost(res)
    assert list(out) == ["latency_p95_s"]
    assert out["latency_p95_s"]["ratios"] == [pytest.approx(1.1), 1.0]
    assert out["latency_p95_s"]["median"] == pytest.approx(1.05)


def test_script_runs_a_cell_at_cpu_size(tmp_path, capsys):
    out = tmp_path / "lines.jsonl"
    load = trace.load_events
    assert S.main(["--workload", "pair.vqa-poisson", "--seeds",
                   str(2 ** 33 + 5), "--traced-seed", str(2 ** 33 + 6),
                   "--seconds", "2", "--settle", "120", "--shrink",
                   "--out", str(out)]) == 0
    assert trace.load_events is load
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert [(x["spans"], x["traced"]) for x in lines[:3]] == [
        ("off", False), ("on", False), ("on", True)]
    off, on, traced, summary = lines
    # the CPU has no device plane: the trace is read, nothing is busy
    assert traced["busy_s"] == 0.0 and traced["failed"] == 0
    assert traced["spans_s_traced"]["moa.engine.step"][0] > 0
    for res in (off, on):
        assert res["failed"] == 0 and res["attempted"] > 0
        assert res["settled"]
        assert math.isfinite(res["metrics"]["latency_p95_s"])
        assert res["metrics"]["queue_wait_p95_ms"] >= 0.0
    assert "host_ms_per_block" not in off["metrics"]
    assert "spans_s" not in off
    assert on["metrics"]["host_ms_per_block"] > 0.0
    assert on["spans_s"]["moa.engine.block"][0] > 0
    assert set(summary["on_over_off"]) == {"latency_p95_s", "tpot_p95_ms"}
    printed = capsys.readouterr().out.splitlines()
    assert [ln.split(" ", 1)[0] for ln in printed] == [
        "RESULT", "RESULT", "RESULT", "SUMMARY"]
