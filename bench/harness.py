"""One run of one benchmark cell: load, warm up, measure, check, report.

Everything that belongs to a configuration, a traffic mix or a metric is
data found by name: ``BENCHMARK.json`` pairs a configuration
(``configs/<name>.json`` with its reference module beside it) with a mix
(``traffic/<name>.json``, read by ``gen.py``), and each metric is a reader
``metrics/<name>.py`` with ``read(run) -> float | None``. The run record
the readers get is built here and holds what the window drove: requests
with their due times and outcomes, counters of the program at the
window's two ends and, in a traced run, the reduced trace and a log of
the work each jitted call was given.

From the program it imports only what the window drives: the model
builder, ``TierEngine``, ``ClusterServer``, the scheduler and its policy.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import gen, trace  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.peaks import peaks as peak_table  # noqa: E402

# sizes the CPU tests shrink every model to (no cell runs them)
TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 2048, "num_patches": 8, "frontend_dim": 32}
TINY_SERVING = {"max_seq": 256, "kv_page_size": 32}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process started (kernel clock ticks)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------------------
# what a cell is made of, found by name


def _by_name(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cell = _by_name(bm["workloads"], name, "workload")
    centry = _by_name(bm["configs"], cell["config"], "config")
    conf = json.loads((root / centry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    return SimpleNamespace(
        name=name, cell=cell, conf=conf, mix=mix,
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    return load_module(path, "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_")).read


def reference_module(conf: dict, root: Path = ROOT):
    path = root / "bench" / "configs" / f"{conf['reference']}.py"
    return load_module(path, "bench_ref_" + conf["reference"])


def shrink(cell: SimpleNamespace) -> None:
    """CPU test sizes: tiny models, short prompts, small images."""
    for t in cell.conf["tiers"].values():
        t["model"].update(TINY_MODEL)
    cell.conf["serving"].update(TINY_SERVING)
    mix = cell.mix
    for key, hi in (("prompt_tokens", 48), ("output_tokens", 24)):
        spec = mix[key]
        spec["max"] = min(spec["max"], hi)
        spec["min"] = min(spec["min"], spec["max"] // 2)
        spec["median"] = min(spec["median"], (spec["min"] + spec["max"]) / 2)
    if "image" in mix:
        mix["image"]["shapes"] = [[24, 40], [32, 32]]
        mix["image"]["pool_per_shape"] = 2


# ---------------------------------------------------------------------------
# the system under test


def model_key(seed: int, tier: str):
    return W.leaf_key(W.seed_key(seed), "tier:" + tier)


def build(cell: SimpleNamespace, seed: int):
    """Engines, topology and server of the cell's configuration."""
    from repro.config import ClusterTopology, ModelConfig, ServingConfig
    from repro.config import TierSpec
    from repro.core.baselines import make_policy
    from repro.core.scheduler import MoAOffScheduler
    from repro.models import build_model
    from repro.serving.engine import TierEngine
    from repro.serving.tiers import ClusterServer

    conf = cell.conf
    serving = ServingConfig(**conf["serving"])
    topo = ClusterTopology(conf["topology"]["name"], tuple(
        TierSpec(**t) for t in conf["topology"]["tiers"]))
    engines = {}
    for tier, spec in conf["tiers"].items():
        model = build_model(ModelConfig(**spec["model"]))
        params = W.make_params(model_key(seed, tier), model.param_shapes())
        # EOS can never be sampled: output lengths are the traffic's
        engines[tier] = TierEngine(model, params, serving, eos_id=-1)
    sched = MoAOffScheduler(policy=make_policy(conf["policy"],
                                               topology=topo))
    server = ClusterServer(engines, topology=topo, scheduler=sched)
    return engines, server


# ---------------------------------------------------------------------------
# warm-up: every shape this cell's traffic can give each jitted program


def _next_pow2(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def prefill_shapes(eng, items, images) -> set:
    """(bucket, with patches, rows, pages, padded) of every prefill group
    the engine's bucketed admission can form from these requests: a group
    shares a bucket and extras, pads its rows to a power of two up to the
    slots, and scatters the most pages any member reserved."""
    sv, cfg = eng.serving, eng.cfg
    sigs: Dict[tuple, dict] = {}
    for it in items:
        n = len(it.prompt)
        vis = cfg.num_patches if (it.image is not None
                                  and cfg.frontend == "vision_stub") else 0
        bucket = min(_next_pow2(n, 16), max(n, sv.max_seq - vis))
        total = min(vis + n + it.max_new, sv.max_seq)
        pages = max(1, -(-total // sv.kv_page_size))
        s = sigs.setdefault((bucket, vis > 0), {"pages": set(), "n": 0,
                                                "full": False})
        s["pages"].add(pages)
        s["n"] += 1
        s["full"] |= n == bucket
    out = set()
    for (bucket, img), s in sigs.items():
        rmax = _next_pow2(min(s["n"], sv.max_batch), 1)
        rows = [r for r in (1, 2, 4, 8, 16, 32, 64, 128) if r <= rmax]
        for r in rows:
            for pg in s["pages"]:
                out.add((bucket, img, r, pg, True))
                if s["full"]:
                    out.add((bucket, img, r, pg, False))
    return out


def prefill_call(eng, shape):
    """The engine's prefill-and-insert program and arguments for one
    group shape, all rows pointed at the null page."""
    import jax.numpy as jnp
    import numpy as np

    bucket, img, rows, npg, padded = shape
    cfg = eng.cfg
    batch = {"tokens": jnp.asarray(np.zeros((rows, bucket), np.int32))}
    if img:
        batch["patches"] = jnp.asarray(np.zeros(
            (rows, cfg.num_patches, cfg.frontend_dim), np.float32))
    if padded:
        batch["lengths"] = jnp.asarray(np.ones((rows,), np.int32))
    slots = jnp.asarray(np.arange(rows, dtype=np.int32) % len(eng.slots))
    total = jnp.asarray(np.ones((rows,), np.int32))
    pt = jnp.asarray(np.zeros((rows, eng._n_pt), np.int32))

    def call():
        logits, eng.cache = eng._prefill_insert(
            eng.params, eng.cache, batch, slots, total, pt, npg)
        np.asarray(logits)

    return eng._prefill_insert, (eng.params, eng.cache, batch, slots, total,
                                 pt, npg), call


def decode_buckets(eng, items) -> List[int]:
    """Context buckets a fused block can ask for: every ladder value up to
    the one covering the longest request."""
    cfg, sv = eng.cfg, eng.serving
    longest = max(len(it.prompt) + it.max_new
                  + (cfg.num_patches if it.image is not None else 0)
                  for it in items)
    top = eng._context_bucket(min(longest, sv.max_seq) + eng.fused_steps + 1)
    out, t = [], 32
    while True:
        b = eng._context_bucket(t)
        if not out or b != out[-1]:
            out.append(b)
        if b >= top:
            return out
        t = b + 1


def decode_call(eng, teff: int):
    """The engine's fused decode block and arguments at one context
    bucket, every slot idle."""
    import jax.numpy as jnp
    import numpy as np

    z = jnp.asarray(np.zeros((len(eng.slots),), np.int32))

    def call():
        block, eng.cache, eng._keys = eng._fused(
            eng.params, eng.cache, eng._keys, z, z, z, teff, eng.fused_steps)
        np.asarray(block)

    return eng._fused, (eng.params, eng.cache, eng._keys, z, z, z, teff,
                        eng.fused_steps), call


def on_big_stack(fn, *args):
    """Run ``fn`` on a thread with a 1 GiB stack: the TPU compiler recurses
    deeply on some of these programs, deeper than a default stack holds."""
    import threading

    box = {}

    def body():
        try:
            box["out"] = fn(*args)
        except BaseException as e:  # handed back to the caller, re-raised
            box["err"] = e

    old = threading.stack_size(1 << 30)
    try:
        t = threading.Thread(target=body, name="bench-warm-up")
        t.start()
    finally:
        threading.stack_size(old)
    t.join()
    if "err" in box:
        raise box["err"]
    return box["out"]


def warm_up(cell, engines, server, items, images) -> dict:
    """Compile (or load from the cache) every program the window can call,
    one after another."""
    counts = {}
    shapes = sorted({im.shape for im in images})
    probe = items[0]
    for shape in shapes:  # scorer: image_stats kernel + score arithmetic
        img = next(im for im in images if im.shape == shape)
        server.scheduler.score(server.build_request(
            "", image=img, max_new=probe.max_new, prompt_ids=probe.prompt))
    server.scheduler.score(server.build_request(
        "", max_new=probe.max_new, prompt_ids=probe.prompt))
    programs = []
    for tier, eng in engines.items():
        if eng.cfg.frontend == "vision_stub":
            geoms = {(e.cfg.num_patches, e.cfg.frontend_dim)
                     for e in engines.values()}
            for shape in shapes:
                img = next(im for im in images if im.shape == shape)
                for p, fd in sorted(geoms):
                    eng.encode_image(img, p, fd)
        pre = sorted(prefill_shapes(eng, items, images))
        dec = decode_buckets(eng, items)
        programs += [prefill_call(eng, s) for s in pre]
        programs += [decode_call(eng, teff) for teff in dec]
        counts[tier] = {"prefill": len(pre), "decode": len(dec)}
    for _, _, call in programs:
        call()
    for eng in engines.values():
        eng._release_pages(0)  # the eager page-table reset of a finish
    return counts


# ---------------------------------------------------------------------------
# compilations inside the window


class CompileCounter:
    """Counts tracing and backend compilation events while armed."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.armed = False
        self.traces = 0
        self.compiles = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if not self.armed:
            return
        if event == self.TRACE:
            self.traces += 1
            self.names.append(str(kw.get("fun_name", "?")))
        elif event == self.COMPILE:
            self.compiles += 1


# ---------------------------------------------------------------------------
# harness spans and the work log of a traced run


def instrument(engines, server, work: list) -> None:
    """Wrap the program's entry points, on these objects only, in harness
    spans, and log the work each jitted call is given."""
    import jax
    import numpy as np

    def span(name, fn):
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return wrapped

    for tier, eng in engines.items():
        fused, pre = eng._fused, eng._prefill_insert

        def fused_logged(*a, _tier=tier, _f=fused):
            work.append(("decode", time.monotonic(), _tier, np.asarray(a[4]),
                         np.asarray(a[5]), int(a[7])))
            with jax.profiler.TraceAnnotation("bench.engine.fused"):
                return _f(*a)

        def prefill_logged(*a, _tier=tier, _f=pre):
            batch = a[2]
            rows = len(set(np.asarray(a[3]).tolist()))
            toks = batch["tokens"].shape[1]
            lengths = (np.asarray(batch["lengths"])[:rows]
                       if "lengths" in batch else np.full(rows, toks))
            patches = (batch["patches"].shape[1] if "patches" in batch
                       else 0)
            work.append(("prefill", time.monotonic(), _tier, lengths,
                         patches))
            with jax.profiler.TraceAnnotation("bench.engine.prefill"):
                return _f(*a)

        eng._fused = fused_logged
        eng._prefill_insert = prefill_logged
        eng.step = span("bench.engine.step", eng.step)
        eng._admit = span("bench.engine.admit", eng._admit)
        eng.encode_image = span("bench.engine.encode", eng.encode_image)

    sched = server.scheduler
    score = sched.score

    def score_logged(req):
        img = req.modalities.get("image")
        if img is not None and img.data is not None:
            work.append(("score", time.monotonic(), np.shape(img.data)))
        with jax.profiler.TraceAnnotation("bench.scheduler.score"):
            return score(req)

    sched.score = score_logged
    rt = server.runtime
    rt.backend.advance = span("bench.runtime.advance", rt.backend.advance)
    rt.handlers["arrival"] = span("bench.runtime.arrival",
                                  rt.handlers["arrival"])


# ---------------------------------------------------------------------------
# the measured window


def counters(engines, server) -> dict:
    out = {"t": time.monotonic(),
           "score_time_s": server.scheduler.score_time_s,
           "n_scored": server.scheduler.n_scored}
    for tier, eng in engines.items():
        out[tier] = {"decode_tokens": eng.decode_tokens, "steps": eng.steps}
    return out


def run_until(rt, t_end: float) -> None:
    while True:
        left = t_end - time.monotonic()
        if left <= 0:
            return
        rt.run(max_wall_s=left)
        if not rt.events and not any(rt.backend._inflight.values()):
            time.sleep(max(0.0, t_end - time.monotonic()))


def schedule(cell, seed: int, seconds: float, rate: Optional[float] = None):
    """The run's requests (at ``rate`` if given) and image pool pixels."""
    mix = json.loads(json.dumps(cell.mix))
    if rate is not None:
        mix["arrivals"]["rate_rps"] = rate
    vocab = min(t["model"]["vocab_size"] for t in cell.conf["tiers"].values())
    items, pool = gen.schedule(mix, seed, seconds, vocab)
    return items, gen.make_pool(pool, seed)


def set_up(cell, seed: int, items, images) -> SimpleNamespace:
    """Engines and server of the cell, every program the items can call
    compiled or loaded."""
    import jax

    engines, server = build(cell, seed)
    jax.block_until_ready([e.params for e in engines.values()])
    t_built = process_age_s()
    warm = on_big_stack(warm_up, cell, engines, server, items, images)
    log(f"warm-up: {json.dumps(warm)} programs per tier, "
        f"{process_age_s() - t_built:.3f} s")
    return SimpleNamespace(engines=engines, server=server, warm=warm,
                           counter=CompileCounter())


def drive(cell, st, items, images, seconds: float, traced: bool
          ) -> SimpleNamespace:
    """Submit the items open loop, measure the window, drain; return
    plain data only."""
    import jax

    engines, server = st.engines, st.server
    work: list = []
    if traced:
        instrument(engines, server, work)
    counter = st.counter
    counter.traces = counter.compiles = 0
    counter.names = []
    rt = server.runtime
    ramp = cell.mix["arrivals"]["ramp_s"]
    t0 = time.monotonic() + 0.5
    reqs = {}
    for it in items:
        img = images[it.image] if it.image is not None else None
        req = server.build_request("", image=img, max_new=it.max_new,
                                   slo_s=it.slo_s, prompt_ids=it.prompt)
        req.arrival_s = t0 + it.due_s
        server.submit_request(req)
        reqs[req.rid] = (it, req)
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    counter.armed = True
    run_until(rt, w0)
    c0 = counters(engines, server)
    setup_s = process_age_s()
    red, trace_t = None, (0.0, 0.0)
    if traced:
        tr_s = min(5.0, 0.4 * seconds)
        run_until(rt, w0 + 0.3 * seconds)
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                trace_t = (time.monotonic(), time.monotonic() + tr_s)
                run_until(rt, trace_t[1])
                trace_t = (trace_t[0], time.monotonic())
            jax.profiler.stop_trace()
            red = trace.reduce_events(trace.load_events(
                trace.find_xplane(tdir)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    run_until(rt, w1)
    c1 = counters(engines, server)
    counter.armed = False
    log(f"window: {counter.traces} traces, {counter.compiles} backend "
        f"compiles inside the window {sorted(set(counter.names))}")
    rt.run(max_wall_s=cell.mix["drain_s"])
    t_end = time.monotonic()
    results = {r.rid: r for r in server.run(timeout_s=0.0)}
    rows = []
    for rid, (it, req) in sorted(reqs.items()):
        res = results.get(rid)
        img = req.modalities.get("image")
        rec = rt.records.get(rid)
        rows.append(SimpleNamespace(
            rid=rid, item=it, due=req.arrival_s,
            in_window=w0 <= req.arrival_s < w1,
            done=res is not None and not res.failed,
            failed=res is not None and res.failed,
            tier=res.tier if res is not None else None,
            tokens=list(res.tokens) if res is not None else [],
            ttft_s=res.ttft_s if res is not None else math.inf,
            latency_s=res.latency_s if res is not None else math.inf,
            truncated=bool(res.truncated) if res is not None else False,
            wan_s=rec.wan_s if rec is not None else 0.0,
            score=img.complexity if img is not None else None))
    return SimpleNamespace(
        rows=rows, images=images, c0=c0, c1=c1, work=work, trace=red,
        trace_t=trace_t, t_end=t_end, setup_s=setup_s, warm=st.warm,
        window_traces=counter.traces, window_compiles=counter.compiles,
        w0=w0, w1=w1)


def memory_peak(chips: int):
    import jax

    devs = jax.devices()[:chips]
    return devs, int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devs))


# ---------------------------------------------------------------------------
# correctness: the served tokens and the scores against the references


def sample_requests(rows, tier: str, seed: int, target_tokens: int = 1024,
                    most: int = 32) -> list:
    """Finished window requests of one tier, drawn from the seed, with the
    longest in it, until about a thousand served tokens: greedy tokens
    differ from the reference's best only at near ties, so the number
    compared needs many positions to read the same from seed to seed."""
    import numpy as np

    cand = [r for r in rows if r.in_window and r.done and r.tier == tier]
    if not cand:
        return []
    cand.sort(key=lambda r: (-len(r.tokens), r.rid))
    rng = np.random.default_rng([int(seed), 2])
    rest = [cand[i] for i in rng.permutation(len(cand) - 1) + 1]
    out, n = [cand[0]], len(cand[0].tokens)
    for r in rest:
        if n >= target_tokens or len(out) >= most:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def widest_gap(ref_logits, chosen) -> float:
    """Largest amount by which a chosen token's reference logit lies below
    the reference's best, over every position."""
    import numpy as np

    worst = 0.0
    for lg, toks in zip(ref_logits, chosen):
        toks = np.asarray(toks)
        best = lg.max(axis=-1)
        worst = max(worst, float(np.max(best - lg[np.arange(len(toks)),
                                                  toks])))
    return worst


def served_check(cell, out, seed: int, control: Optional[str] = None
                 ) -> Dict[str, float]:
    """Readings compared in a run: the widest logit gap over every served
    tier's sample, the widest image-score gap, and outputs cut short.
    With ``control="fp8"`` the tokens compared are the ones the
    lower-precision reference puts first, and the image scores are the
    bfloat16 reference's."""
    import jax.numpy as jnp
    import numpy as np

    from bench import image_ref

    ref = reference_module(cell.conf)
    readings: Dict[str, float] = {}
    win = [r for r in out.rows if r.in_window and r.done]
    readings["short_outputs"] = float(sum(
        len(r.tokens) != r.item.max_new or r.truncated for r in win))
    readings["failed_requests"] = float(sum(
        r.failed for r in out.rows if r.in_window))
    gaps, served = [], 0
    for tier, spec in cell.conf["tiers"].items():
        sample = sample_requests(out.rows, tier, seed)
        if not sample:
            continue
        seqs = [{"prompt": r.item.prompt,
                 "served": np.asarray(r.tokens, np.int32),
                 "image": (out.images[r.item.image]
                           if r.item.image is not None else None)}
                for r in sample]
        key = model_key(seed, tier)
        logits = ref.served_logits(spec["model"], key, seqs)
        if control is None:
            chosen = [s["served"] for s in seqs]
        else:
            low = ref.served_logits(spec["model"], key, seqs, quant=control)
            chosen = [lg.argmax(axis=-1) for lg in low]
            del low
        gaps.append(widest_gap(logits, chosen))
        served += sum(len(s["served"]) for s in seqs)
    if gaps:
        readings["logit_gap"] = max(gaps)
        readings["served_tokens"] = float(served)
    scored = {}
    for r in win:
        if r.item.image is not None and r.score is not None:
            scored.setdefault(r.item.image, r.score)
    if scored:
        dt = jnp.float32 if control is None else jnp.bfloat16
        readings["image_score_gap"] = max(
            abs(image_ref.image_score(out.images[i], dt) - s)
            for i, s in scored.items())
    return readings


# counts that have to read nought in every run
EXACT = ("short_outputs", "failed_requests")


def decide(readings: Dict[str, float], limits: Dict[str, float]):
    """Every compared number against its limit; a number without a limit
    fails (no limit has been measured for it)."""
    checks = {}
    for name, value in readings.items():
        if name == "served_tokens":
            continue
        lim = 0.0 if name in EXACT else limits.get(name)
        checks[name] = {"value": value, "limit": lim}
    ok = all(c["limit"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


# ---------------------------------------------------------------------------
# one run


def use_cache(jax) -> None:
    """Keep every compiled program, however small or quick, in the
    checkout's cache, with no size cap that could evict one."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, root: Path = ROOT) -> int:
    cell = load_cell(args.workload, root)
    import jax

    use_cache(jax)
    devices = jax.devices()
    dev = devices[0]
    chips = cell.cell["chips"]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    if dev.platform != "tpu":
        log(f"no TPU: JAX found platform {dev.platform!r}")
        return 2
    if len(devices) < chips:
        log(f"cell needs {chips} chips, JAX found {len(devices)}")
        return 2
    peak = peak_table(dev.device_kind)

    items, images = schedule(cell, args.seed, args.seconds)
    st = set_up(cell, args.seed, items, images)
    out = drive(cell, st, items, images, args.seconds, bool(args.trace))
    devs, out.memory_peak_bytes = memory_peak(chips)
    del st
    gc.collect()  # the program's state goes before the reference runs
    win = [r for r in out.rows if r.in_window]
    log(f"requests: {len(win)} due in the window, "
        f"{sum(r.done for r in win)} finished, "
        f"{sum(r.failed for r in win)} failed, setup {out.setup_s:.3f} s, "
        f"memory peak {out.memory_peak_bytes} bytes")

    t_ref = time.monotonic()
    readings = served_check(cell, out, args.seed)
    ok, checks = decide(readings, cell.conf.get("limits", {}))
    log(f"reference check: {time.monotonic() - t_ref:.3f} s")

    record = SimpleNamespace(cell=cell, out=out, rows=win, peak=peak,
                             seconds=args.seconds,
                             models={t: s["model"] for t, s in
                                     cell.conf["tiers"].items()})
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = metric_reader(m["name"], root)(record)
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": ok, "attempted": len(win),
              "failed": sum(r.failed for r in win),
              "metrics": metrics, "device": device}
    if args.trace and out.trace is not None:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        result["breakdown"] = trace.breakdown(out.trace)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
