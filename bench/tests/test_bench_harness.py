"""CPU tests of the benchmark's yardstick: trace reduction, traffic,
tail arithmetic, operation and byte counts, the peaks table, seeded
weights, and the harness finding cells, mixes and metrics by name."""
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, gen, harness, metrics_common, stats, trace  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.peaks import peaks  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _model(config: str, tier: str) -> dict:
    conf = json.loads((ROOT / "bench" / "configs"
                       / f"{config}.json").read_text())
    return conf["tiers"][tier]["model"]


# -- trace reduction ---------------------------------------------------------


def _events():
    """Two device planes' ops and programs, host spans and the window."""
    ops = [("%fusion.1 = bf16[8] fusion(x)", 1.0, 0.2),
           ("%decode_attention_paged.65 = bf16[8] custom-call(y)", 1.3, 0.1),
           ("%fusion.1 = bf16[8] fusion(x)", 1.6, 0.2),
           ("%copy.2 = bf16[8] copy(x)", 0.5, 0.7),
           ("%while.4 = (s32[]) while(z)", 1.55, 0.1)]
    return {
        "devices": {
            "/device:TPU:0": {"XLA Ops": ops,
                              "XLA Modules": [("jit_fused", 1.0, 0.45),
                                              ("jit_fused", 1.6, 0.2)]},
            "/device:TPU:1": {"XLA Ops": []},
        },
        "host": [("bench.window", 1.0, 1.0),
                 ("bench.engine.step", 1.0, 0.9),
                 ("bench.engine.admit", 1.45, 0.1),
                 ("bench.runtime.advance", 1.85, 0.15)],
    }


def test_trace_reduction_on_a_small_trace():
    red = trace.reduce_events(_events())
    # busy: [1.0,1.2] (copy clipped to it) [1.3,1.4] [1.55,1.8]
    assert red["window_s"] == pytest.approx(1.0)
    assert red["planes"] == 1
    assert red["busy_s"] == pytest.approx(0.55)
    assert red["ops"]["fusion"] == [2, pytest.approx(0.4)]
    assert red["ops"]["decode_attention_paged"] == [1, pytest.approx(0.1)]
    assert red["ops"]["copy"][1] == pytest.approx(0.2)
    assert "while" not in red["ops"]  # a loop is busy, not op time
    assert red["modules"]["jit_fused"] == [2, pytest.approx(0.65)]
    # gaps: 1.2-1.3 (step), 1.4-1.55 (admit covers mid 1.475), 1.8-2.0
    # (advance covers mid 1.9)
    assert red["gaps"]["bench.engine.step"] == pytest.approx(0.1)
    assert red["gaps"]["bench.engine.admit"] == pytest.approx(0.15)
    assert red["gaps"]["bench.runtime.advance"] == pytest.approx(0.2)
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "fusion"
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(0.45)


def test_trace_reduction_on_a_recorded_chip_trace():
    ev = json.loads((DATA / "trace_v5e_excerpt.json").read_text())
    red = trace.reduce_events(ev)
    busy = sum(b - a for a, b in trace.union(
        [(max(s, ev["window"][0]), min(s + d, ev["window"][1]))
         for _, s, d in ev["devices"]["/device:TPU:0"]["XLA Ops"]
         if s + d > ev["window"][0] and s < ev["window"][1]]))
    assert red["busy_s"] == pytest.approx(busy)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert sum(red["gaps"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_trace_without_window_is_refused():
    ev = _events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce_events(ev)


# -- traffic -----------------------------------------------------------------


MIXES = ["vqa-poisson", "caption-overload", "chat-poisson"]


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_repeats_for_a_seed_and_differs_across_seeds(mix):
    big = 2 ** 33 + 7
    a, pa = gen.schedule(_mix(mix), big, 10.0, 151936)
    b, pb = gen.schedule(_mix(mix), big, 10.0, 151936)
    c, _ = gen.schedule(_mix(mix), big + 1, 10.0, 151936)
    assert pa == pb
    assert [(x.due_s, x.max_new, x.image, x.prompt.tolist()) for x in a] \
        == [(x.due_s, x.max_new, x.image, x.prompt.tolist()) for x in b]
    assert [x.due_s for x in a] != [x.due_s for x in c]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    # every seed gets the same sizes and gaps, in another order
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                      for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    spec = _mix(mix)
    assert all(spec["prompt_tokens"]["min"] <= len(x.prompt)
               <= spec["prompt_tokens"]["max"] for x in a)
    assert all(0 <= t < 151936 for x in a for t in x.prompt)


ARRIVALS = [{"process": "poisson", "rate_rps": 4.0},
            {"process": "burst", "rate_rps": 4.0, "burst_size": 4,
             "burst_spacing_s": 0.005}]


@pytest.mark.parametrize("arr", ARRIVALS, ids=lambda a: a["process"])
def test_arrivals_repeat_for_a_seed_at_the_mix_rate(arr):
    big = 2 ** 33 + 7
    a = gen.arrival_times(arr, 400, np.random.default_rng(big))
    b = gen.arrival_times(arr, 400, np.random.default_rng(big))
    c = gen.arrival_times(arr, 400, np.random.default_rng(big + 1))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == 400 and np.all(np.diff(a) >= 0) and a[0] >= 0
    assert 400 / a[-1] == pytest.approx(4.0, rel=0.1)


def test_image_pool_repeats_for_a_seed():
    _, pool = gen.schedule(_mix("vqa-poisson"), 5, 4.0, 151936)
    small = [((48, 64), c) for _, c in pool[:3]]
    a, b = gen.make_pool(small, 5), gen.make_pool(small, 5)
    c = gen.make_pool(small, 6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert all(x.dtype == np.float32 and x.min() >= 0 and x.max() <= 255
               for x in a)


def test_box_blur_matches_convolve():
    rng = np.random.default_rng(0)
    img = rng.normal(size=(9, 13)).astype(np.float32)
    for k in (3, 5):
        want = np.apply_along_axis(
            lambda r: np.convolve(r, np.ones(k) / k, mode="same"), 1, img)
        np.testing.assert_allclose(gen._box_blur(img, k, 1), want,
                                   rtol=1e-5, atol=1e-5)


# -- tails -------------------------------------------------------------------


def test_percentile_counts_missing_requests_last():
    assert stats.percentile_rank([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    vals = [0.1] * 94 + [math.inf] * 6
    assert math.isinf(stats.percentile_rank(vals, 95))
    assert stats.percentile_rank([0.1] * 95 + [math.inf] * 5, 95) == 0.1


def test_tail_reports_the_wait_of_a_missing_request():
    rows = [SimpleNamespace(done=True, ttft_s=0.5, due=0.0)] * 3 + [
        SimpleNamespace(done=False, ttft_s=math.inf, due=10.0)]
    run = SimpleNamespace(rows=rows, out=SimpleNamespace(t_end=70.0))
    assert metrics_common.tail(run, "ttft_s", 95) == 60.0
    assert metrics_common.tail(run, "ttft_s", 50) == 0.5


def _tpot_run(done: int, missing: int):
    rows = [SimpleNamespace(done=True, latency_s=1.0 + i * 0.01,
                            ttft_s=0.2, tokens=[1] * 9, due=0.0,
                            item=SimpleNamespace(max_new=9))
            for i in range(done)]
    rows += [SimpleNamespace(done=False, latency_s=math.inf,
                             ttft_s=math.inf, tokens=[], due=4.0,
                             item=SimpleNamespace(max_new=9))] * missing
    rows.append(SimpleNamespace(done=True, latency_s=0.1, ttft_s=0.1,
                                tokens=[1], due=0.0,
                                item=SimpleNamespace(max_new=1)))
    return SimpleNamespace(rows=rows, out=SimpleNamespace(t_end=12.0))


@pytest.mark.parametrize("done,missing,want", [
    (20, 0, (1.0 + 18 * 0.01 - 0.2) / 8 * 1e3),
    (19, 1, (1.0 + 18 * 0.01 - 0.2) / 8 * 1e3),  # missing ranks last
    (18, 2, (12.0 - 4.0) / 8 * 1e3),  # the tail lands on a missing one
])
def test_tpot_tail_counts_unfinished_requests(done, missing, want):
    run = _tpot_run(done, missing)
    assert metrics_common.tpot_tail(run, 95) == pytest.approx(want)


def test_spread_is_the_quartile_gap_over_the_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


# -- operations and bytes ------------------------------------------------------


def test_counts_match_the_published_parameter_counts():
    for tier, total in (("edge", 1_545_819_648), ("cloud", 2_959_011_328)):
        m = _model("moa-pair", tier)
        d, vp = m["d_model"], -(-m["vocab_size"] // 256) * 256
        n = (m["num_layers"] * (flops.layer_matmul_params(m) + 2 * d) + d
             + vp * d * (1 if m["tie_embeddings"] else 2)
             + m["frontend_dim"] * d)
        assert n == total


def test_flop_and_byte_counts_by_hand():
    edge, cloud = _model("moa-pair", "edge"), _model("moa-pair", "cloud")
    # 1536*1536 (q) + 2*1536*256 (k, v) + 1536*1536 (o) + 1536*17920
    # + 8960*1536
    assert flops.layer_matmul_params(edge) == 46_792_704
    assert flops.kv_bytes(edge, 1) == 28 * 2 * 2 * 128 * 2 == 28_672
    assert flops.kv_bytes(cloud, 1000) == 8 * 1000 * 2 * 4 * 128 * 2
    # one decode token at 100 keys: 2*28*46792704 + 4*28*12*128*100
    # + 2*1536*151936
    assert flops.decode_flops(edge, 100) == (
        2_620_391_424 + 17_203_200 + 466_747_392)
    # a prefill of 10 text tokens after 4 image rows: 14 tokens, 105
    # causal pairs
    assert flops.prefill_flops(edge, 10, 4) == (
        2 * 28 * 46_792_704 * 14 + 4 * 28 * 12 * 128 * 105
        + 2 * 4 * 1280 * 1536 + 2 * 1536 * 151936)
    peak = peaks("TPU v5 lite")
    assert flops.least_time(197e12, 0, peak) == pytest.approx(1.0)
    assert flops.least_time(0, 819e9, peak) == pytest.approx(1.0)
    assert flops.image_bytes(1024, 1024) == 4 * 1024 * 1024


def test_decode_least_time_counts_live_slot_steps_only():
    m = _model("moa-pair", "edge")
    peak = peaks("TPU v5 lite")
    w = ("decode", 1.0, "edge", np.array([10, 0, 20]), np.array([2, 0, 8]),
         8)
    run = SimpleNamespace(out=SimpleNamespace(work=[w], trace_t=(0.0, 2.0)),
                          models={"edge": m}, peak=peak)
    # slot 0 lives 2 steps (ctx 11, 12), slot 2 all 8 (ctx 21..28)
    assert sorted(metrics_common.decode_contexts(w)) == [11, 12] + list(
        range(21, 29))
    want = sum(flops.least_time(
        sum(flops.decode_attn_flops(m, c) for c in cs),
        sum(flops.kv_bytes(m, c) for c in cs), peak)
        for cs in ([11, 21], [12, 22]) + tuple([c] for c in range(23, 29)))
    assert metrics_common.decode_least_time(run) == pytest.approx(want)


# -- peaks -------------------------------------------------------------------


def test_peaks_table_knows_v5e_and_refuses_other_devices():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


# -- seeded weights ----------------------------------------------------------


def test_weights_repeat_for_a_seed_and_differ_across_seeds():
    import jax
    import jax.numpy as jnp

    shapes = {"embed": jax.ShapeDtypeStruct((8, 4), jnp.bfloat16),
              "blocks": {"wq": jax.ShapeDtypeStruct((3, 4, 6), jnp.bfloat16),
                         "ln1": jax.ShapeDtypeStruct((3, 4), jnp.bfloat16)}}
    key = W.leaf_key(W.seed_key(2 ** 40 + 3), "tier:edge")
    a, b = W.make_params(key, shapes), W.make_params(key, shapes)
    assert all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    wq = a["blocks"]["wq"]
    assert wq.dtype == jnp.bfloat16
    assert not bool(jnp.array_equal(wq[0], wq[1]))  # layers differ
    assert float(jnp.std(wq.astype(jnp.float32))) == pytest.approx(0.5,
                                                                  rel=0.3)
    other = W.make_params(W.leaf_key(W.seed_key(3), "tier:edge"), shapes)
    assert not bool(jnp.array_equal(a["embed"], other["embed"]))


def test_seeds_past_32_bits_give_different_keys():
    import jax.numpy as jnp

    a, b = W.seed_key(5), W.seed_key(5 + 2 ** 32)
    assert not bool(jnp.array_equal(a, b))


# -- cells, mixes and metrics found by name ------------------------------------


def test_new_config_mix_and_metric_need_only_new_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/vl2b-edge.json").read_text())
    conf["name"] = "vl2b-small-batch"
    conf["serving"]["max_batch"] = 16
    (tmp_path / "bench/configs/vl2b-small-batch.json").write_text(
        json.dumps(conf))
    mix = _mix("chat-poisson")
    mix["arrivals"] = {"process": "burst", "rate_rps": 1.0, "ramp_s": 3.0,
                       "burst_size": 4, "burst_spacing_s": 0.01}
    (tmp_path / "bench/traffic/chat-slow.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/due_requests.py").write_text(
        "def read(run):\n    return float(len(run.rows))\n")
    bm["configs"].append({"name": "vl2b-small-batch", "source": "x",
                          "file": "bench/configs/vl2b-small-batch.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "vl2b.chat-slow",
                            "config": "vl2b-small-batch",
                            "traffic": "chat-slow", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "due_requests", "unit": "requests",
                            "better": "higher", "source": "program_counter",
                            "layer": "runtime", "moves": "tpot_p95_ms",
                            "workloads": ["vl2b.chat-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.load_cell("vl2b.chat-slow", tmp_path)
    assert cell.conf["serving"]["max_batch"] == 16
    assert cell.mix["arrivals"]["rate_rps"] == 1.0
    assert [m["name"] for m in cell.per_layer if "workloads" in m
            and "vl2b.chat-slow" in m["workloads"]] == ["due_requests"]
    read = harness.metric_reader("due_requests", tmp_path)
    assert read(SimpleNamespace(rows=[1, 2])) == 2.0
    assert harness.reference_module(cell.conf, tmp_path).served_logits
    items, _ = gen.schedule(cell.mix, 1, 10.0, 151936)
    assert len(items) == gen.request_count(cell.mix, 10.0)
    gaps = np.diff([it.due_s for it in items])
    assert np.allclose(gaps[:3], 0.01)  # the first clump of four


def test_every_metric_of_the_benchmark_has_a_reader():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for c in bm["workloads"]:
        cell = harness.load_cell(c["name"])
        assert cell.mix["arrivals"]["rate_rps"] > 0
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)


# -- no chip, no result --------------------------------------------------------


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "pair.vqa-poisson", "--seed", "1", "--seconds", "10", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# -- metric readers on a hand-made run record ----------------------------------


def _record():
    m = _model("moa-pair", "edge")
    rows = [SimpleNamespace(done=True, failed=False, tier="edge",
                            tokens=[1] * 5, ttft_s=0.2, latency_s=1.0,
                            due=10.0, wan_s=0.0,
                            item=SimpleNamespace(max_new=5)),
            SimpleNamespace(done=True, failed=False, tier="cloud",
                            tokens=[1] * 3, ttft_s=0.4, latency_s=0.8,
                            due=10.5, wan_s=0.04,
                            item=SimpleNamespace(max_new=3)),
            SimpleNamespace(done=False, failed=False, tier=None, tokens=[],
                            ttft_s=math.inf, latency_s=math.inf, due=11.0,
                            wan_s=0.0, item=SimpleNamespace(max_new=10))]
    c0 = {"t": 0.0, "score_time_s": 1.0, "n_scored": 10,
          "edge": {"decode_tokens": 100, "steps": 10},
          "cloud": {"decode_tokens": 50, "steps": 5}}
    c1 = {"t": 2.0, "score_time_s": 1.03, "n_scored": 13,
          "edge": {"decode_tokens": 300, "steps": 20},
          "cloud": {"decode_tokens": 90, "steps": 10}}
    tr = {"window_s": 2.0, "busy_s": 1.5, "ops": {"k_paged": [4, 0.01]},
          "modules": {"jit_fused": [4, 0.2], "jit_fn_paged": [2, 0.1]},
          "gaps": {}}
    work = [("prefill", 10.2, "edge", np.array([10]), 4),
            ("score", 10.3, (1024, 1024)),
            ("decode", 10.4, "edge", np.array([14, 0]), np.array([8, 0]), 8),
            ("decode", 99.0, "edge", np.array([14]), np.array([8]), 8)]
    out = SimpleNamespace(c0=c0, c1=c1, trace=tr, trace_t=(10.0, 12.0),
                          rows=rows, work=work, t_end=20.0, setup_s=42.0)
    return SimpleNamespace(out=out, rows=rows, peak=peaks("TPU v5 lite"),
                           models={"edge": m, "cloud": m}, seconds=2.0)


@pytest.mark.parametrize("name,want", [
    ("setup_s", 42.0),
    ("score_ms", 10.0),
    ("cloud_route_share", 50.0),
    ("wan_ms_mean", 40.0 / 3),
    ("tokens_per_block", 240 / 15),
    ("output_tok_per_s", 120.0),
    ("device_idle_share", 25.0),
    ("decode_block_ms", 50.0),
    ("latency_p95_s", 9.0),
    ("tpot_p95_ms", 1000.0),  # the unfinished request: 9 s over 9 tokens
    ("device_busy_ms_per_req", 750.0),
])
def test_metric_reader_arithmetic(name, want):
    assert harness.metric_reader(name)(_record()) == pytest.approx(want)


def test_mfu_counts_the_window_calls_only():
    run = _record()
    m = run.models["edge"]
    pre = flops.prefill_flops(m, 10, 4)
    dec = sum(flops.decode_flops(m, c) for c in range(15, 23))
    want = 100 * (pre + dec) / (2.0 * 197e12)
    assert harness.metric_reader("mfu")(run) == pytest.approx(want)
    assert harness.metric_reader("mfu.overload")(run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["decode_block_ms",
                                  "paged_decode_roofline"])
def test_overload_readers_read_the_same_quantity(name):
    run = _record()
    assert harness.metric_reader(name + ".overload")(run) == \
        harness.metric_reader(name)(run)


def test_readers_find_nothing_without_a_trace():
    run = _record()
    run.out.trace = None
    for name in ("mfu", "mfu.overload", "decode_block_ms",
                 "decode_block_ms.overload", "paged_decode_roofline",
                 "paged_decode_roofline.overload", "image_stats_roofline",
                 "device_idle_share", "device_busy_ms_per_req"):
        assert harness.metric_reader(name)(run) is None


@pytest.mark.parametrize("config,tier", [("moa-pair", "edge"),
                                         ("moa-pair", "cloud")])
def test_reference_draws_the_program_parameter_tree(config, tier):
    """Same names, shapes and dtypes as the program's model, so the one
    jitted draw gives both the same weights."""
    import jax

    from repro.config import ModelConfig
    from repro.models import build_model

    m = dict(_model(config, tier), **harness.TINY_MODEL)
    ref = harness.reference_module({"reference": "qwen2_vl_ref"})
    want = build_model(ModelConfig(**m)).param_shapes()
    got = ref.param_shapes(m)
    leaf = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: (x.shape, str(x.dtype)), t)
    assert leaf(got) == leaf(want)
