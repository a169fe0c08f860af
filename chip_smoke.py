#!/usr/bin/env python3
"""Chip smoke: MoA-Off's edge–cloud serving path on one TPU, at published widths.

Drives the main path once through the entry points a user calls —
``build_cluster_engines`` and ``ClusterServer``, as ``repro.launch.serve``
does — with random bf16 weights from a fixed seed:

* edge tier: qwen2-vl-2b at its full published config (28 layers);
* cloud tier: qwen2.5-vl-7b at full width, depth cut 28 -> 8 layers so both
  tiers and their KV pages fit one 16 GB chip;
* paged KV, ``decode_impl="auto"`` (the Pallas paged kernel), 8 fused decode
  steps per host sync, 8 slots, ``max_seq`` 2048;
* 8 requests, each a prompt of a few hundred tokens plus a 1024x1024 image,
  32 new tokens, under the ``moa-off`` policy. A tier the policy sends
  nothing to serves the same requests under its static policy.

It checks that every request completes with in-vocabulary tokens, that each
Pallas kernel matches its ``jax.numpy`` reference at the served shapes, and
that the lowered fused-decode program calls the kernel (``tpu_custom_call``).
Readings (set-up and compile time, wall time per phase, served counts, peak
device memory) are printed on earlier lines; the last line of stdout is one
JSON object naming the device. Any failed check exits non-zero.

``--four-chips`` runs only the replica phase: the edge tier as four local
replicas, each with its params and KV on its own chip, against the same
requests on one replica. Greedy tokens must be identical and every chip must
hold a replica's bytes.

Run from the repository root on a TPU host (one process holds the chip):

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MAX_NEW = 32
N_REQUESTS = 8
IMAGE_HW = (1024, 1024)  # the scorer's reference resolution
CLOUD_LAYERS = 8

# bf16 attention outputs: unit roundoff 2^-9 ~ 2e-3. The kernels round the
# softmax weights to bf16 before the PV matmul at a different point than
# the references (before vs after normalisation) and accumulate in f32 in
# a different order, so an output carries a few bf16 roundings on values of
# magnitude <= max|v| ~ 4.
ATTN_TOL = 2e-2
# image statistics are f32 sums over 1M pixels in a different order than
# the reference's: relative error well under 1e-4. The Laplacian sum
# telescopes to 0, so both sides are pure rounding; it is held to 1e-5 of
# the Cauchy-Schwarz bound sqrt(n * sum(lap^2)) on sum|lap|. Histogram
# counts are integers and must match exactly.
STATS_RTOL = 1e-4
LAP_SUM_REL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# configuration


def published_configs():
    """{tier: ModelConfig}: the edge model at full size, the cloud model at
    full width with its depth cut to fit one chip beside it."""
    from repro.configs import get_config

    edge = get_config("qwen2-vl-2b")
    cloud = get_config("qwen2.5-vl-7b")
    log(f"reduced: qwen2.5-vl-7b num_layers {cloud.num_layers}->"
        f"{CLOUD_LAYERS} (one-chip fit)")
    return {"edge": edge, "cloud": cloud.replace(num_layers=CLOUD_LAYERS)}


def serving_config():
    from repro.config import ServingConfig

    return ServingConfig(max_batch=8, max_seq=2048, fused_steps=8,
                         decode_impl="auto", paged=True)


def make_requests(seed: int = 0, hw=IMAGE_HW):
    """(text, image) pairs like ``repro.launch.serve`` makes, with prompts
    of a few hundred tokens and images near the scorer's reference size."""
    import numpy as np

    from repro.data.synthetic import make_image

    rng = np.random.default_rng(seed)
    out = []
    for i in range(N_REQUESTS):
        img = make_image(rng, rng.beta(1.6, 1.6), *hw)
        text = (f"Request {i}: describe the Scene {i * 3}. "
                + "and then explain why it matters. "
                * int(rng.integers(40, 50)))
        out.append((text, img))
    return out


# ---------------------------------------------------------------------------
# kernels against their references, at the served shapes


def _close(got, want, tol, what):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    check(bool(np.all(np.isfinite(got))), f"{what}: non-finite output")
    check(bool(np.allclose(got, want, rtol=tol, atol=tol)),
          f"{what}: max |kernel - reference| {err} > tol {tol}")
    return err


def check_kernels(configs, sv, image, seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    b, t, page = sv.max_batch, sv.max_seq, sv.kv_page_size
    n_pt, n_pool = sv.pages_per_slot, sv.pool_pages + 1
    for tier, cfg in configs.items():
        h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
        dt = cfg.dtype
        rnd = lambda *s: jnp.asarray(rng.standard_normal(s), dt)  # noqa: E731
        q = rnd(b, 1, h, hd)
        # rows hold different live lengths; empty slots carry pos -1
        fill = rng.integers(1, t + 1, size=b)
        pos = np.where(np.arange(t)[None] < fill[:, None],
                       np.arange(t)[None], -1).astype(np.int32)
        pq = jnp.asarray(fill - 1, jnp.int32)

        kc, vc = rnd(b, t, kh, hd), rnd(b, t, kh, hd)
        got = ops.decode_attention(q, kc, vc, pq, jnp.asarray(pos))
        want = ref.decode_attention_ref(q, kc, vc, pq, jnp.asarray(pos))
        err = _close(got, want, ATTN_TOL, f"{tier} dense decode")
        log(f"kernel {tier} decode_attention B{b} H{h} KV{kh} T{t} "
            f"{jnp.dtype(dt).name}: max err {err:.3g}")

        kp, vp = rnd(n_pool, page, kh, hd), rnd(n_pool, page, kh, hd)
        pages = np.stack([rng.permutation(np.arange(1, n_pool))[:n_pt]
                          for _ in range(b)]).astype(np.int32)
        got = ops.decode_attention_paged(q, kp, vp, jnp.asarray(pages), pq,
                                         jnp.asarray(pos))
        gather = lambda pool: pool[pages].reshape(b, n_pt * page, kh, hd)  # noqa: E731
        want = ref.decode_attention_ref(q, gather(kp), gather(vp), pq,
                                        jnp.asarray(pos))
        err = _close(got, want, ATTN_TOL, f"{tier} paged decode")
        log(f"kernel {tier} decode_attention_paged B{b} pages {n_pt}x{page} "
            f"of {n_pool}: max err {err:.3g}")

        s = min(1024, t)  # the prompt bucket the served requests fill
        fq, fk, fv = rnd(1, s, h, hd), rnd(1, s, kh, hd), rnd(1, s, kh, hd)
        err = _close(ops.flash_attention(fq, fk, fv),
                     ref.flash_attention_ref(fq, fk, fv), ATTN_TOL,
                     f"{tier} flash attention")
        log(f"kernel {tier} flash_attention S{s} (not on the served path: "
            f"prefill attends through XLA): max err {err:.3g}")

    # the served size, and a ragged crop whose width the kernel pads to
    # 128 lanes and masks
    for img in (image, image[:333, :517]):
        img = jnp.asarray(img, jnp.float32)[None]
        what = f"image_stats {img.shape[1]}x{img.shape[2]}"
        got = jax.tree.map(np.asarray, ops.image_stats(img))
        want = jax.tree.map(np.asarray, ref.image_stats_batch_ref(img))
        for key in ("sobel_sum", "lap_sq_sum"):
            check(bool(np.allclose(got[key], want[key], rtol=STATS_RTOL,
                                   atol=0)),
                  f"{what} {key}: {got[key]} vs {want[key]}")
        bound = LAP_SUM_REL * np.sqrt(img.size * want["lap_sq_sum"])
        check(bool(np.all(np.abs(got["lap_sum"] - want["lap_sum"])
                          <= bound)),
              f"{what} lap_sum: {got['lap_sum']} vs {want['lap_sum']}")
        check(bool(np.array_equal(got["hist"], want["hist"])),
              f"{what}: histogram differs from the reference")
        log(f"kernel {what}: sums within rtol {STATS_RTOL}, histogram "
            f"exact")


# ---------------------------------------------------------------------------
# serving


def serve(engines, topology, policy: str, requests):
    """One ClusterServer run of ``requests`` under ``policy``."""
    from repro.core.baselines import make_policy
    from repro.core.scheduler import MoAOffScheduler
    from repro.serving.tiers import ClusterServer

    server = ClusterServer(engines, topology=topology,
                           scheduler=MoAOffScheduler(
                               policy=make_policy(policy, topology=topology)))
    for text, img in requests:
        server.submit(text, image=img, max_new=MAX_NEW)
    # no server.close(): it retires the pools' replicas, which later runs
    # reuse (local replicas hold no process to join)
    return sorted(server.run(), key=lambda r: r.rid)


def check_results(results, configs, what: str) -> None:
    check(len(results) == N_REQUESTS,
          f"{what}: {len(results)} of {N_REQUESTS} requests returned")
    for r in results:
        check(not r.failed, f"{what}: rid {r.rid} ended {r.fail_reason!r}")
        vocab = configs[r.tier].vocab_size
        check(0 < len(r.tokens) <= MAX_NEW,
              f"{what}: rid {r.rid} produced {len(r.tokens)} tokens")
        check(all(0 <= t < vocab for t in r.tokens),
              f"{what}: rid {r.rid} has a token outside [0, {vocab})")


def served_line(what: str, results, wall: float) -> Counter:
    counts = Counter(r.tier for r in results)
    split = " ".join(f"{t}={n}" for t, n in sorted(counts.items()))
    log(f"phase {what}: {wall:.3f} s wall | served {split}")
    return counts


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]


def lowered_decode_block(eng):
    """An engine's fused decode block, lowered (not run) on its own state
    at full context: its text shows which attention kernel decode calls
    (``tpu_custom_call`` is a Pallas kernel). Served blocks lower at
    smaller context buckets; the kernel choice does not depend on it."""
    import jax.numpy as jnp

    z = jnp.zeros((len(eng.slots),), jnp.int32)
    return eng._fused.lower(eng.params, eng.cache, eng._keys, z, z, z,
                            eng.serving.max_seq, eng.fused_steps)


def one_chip(configs, sv) -> None:
    import jax

    from repro.config import two_tier_topology
    from repro.serving.tiers import build_cluster_engines

    topo = two_tier_topology()
    requests = make_requests()

    t0 = time.perf_counter()
    check_kernels(configs, sv, requests[0][1])
    log(f"phase kernels: {time.perf_counter() - t0:.3f} s wall "
        f"(compile included)")

    t0 = time.perf_counter()
    engines = build_cluster_engines(topo, sv, configs=configs)
    jax.block_until_ready([e.params for e in engines.values()])
    log(f"setup build engines: {time.perf_counter() - t0:.3f} s")
    for tier, eng in engines.items():
        c = eng.cfg
        n = sum(x.size for x in jax.tree.leaves(eng.params))
        log(f"engine {tier}: {c.name} layers={c.num_layers} "
            f"d_model={c.d_model} heads={c.num_heads}/{c.num_kv_heads} "
            f"vocab={c.vocab_size} dtype={c.dtype} params={n}")

    # the first pass compiles every prefill bucket and decode context
    # bucket it meets; the second pass over the same requests is warm
    t0 = time.perf_counter()
    check_results(serve(engines, topo, "moa-off", requests), configs,
                  "warm-up")
    log(f"setup compile (first moa-off pass): "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    results = serve(engines, topo, "moa-off", requests)
    counts = served_line("serve moa-off", results,
                         time.perf_counter() - t0)
    check_results(results, configs, "moa-off")
    for tier in topo.names:
        if counts[tier]:
            continue
        policy = f"{tier}-only"
        t0 = time.perf_counter()
        static = serve(engines, topo, policy, requests)
        served_line(f"serve {policy} (moa-off sent {tier} nothing)", static,
                    time.perf_counter() - t0)
        check_results(static, configs, policy)
        check(all(r.tier == tier for r in static),
              f"{policy}: a request was served off {tier}")

    for tier, eng in engines.items():
        text = lowered_decode_block(eng).as_text()
        check("tpu_custom_call" in text,
              f"{tier}: the fused decode program has no Pallas kernel")
        log(f"{tier}: fused decode program calls the Pallas kernel "
            f"(tpu_custom_call)")
    log(f"peak device memory: {peak_bytes(jax.devices()[:1])[0]} bytes")


def four_chips(configs, sv) -> None:
    import jax

    from repro.config import ClusterTopology, two_tier_topology
    from repro.serving.pool import EnginePool, build_engine_pools

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    devices = devices[:4]
    edge_spec = two_tier_topology().tiers[0]
    topo = ClusterTopology("edge-x4", (edge_spec,))
    requests = make_requests()
    edge = {"edge": configs["edge"]}

    t0 = time.perf_counter()
    pools = build_engine_pools(topo, sv, configs=edge,
                               replicas={"edge": 4}, devices=devices)
    log(f"setup build 4 edge replicas: {time.perf_counter() - t0:.3f} s")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    log(f"bytes in use per device: {in_use}")
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(
        pools["edge"].transports[0].engine.params))
    check(all(b >= param_bytes for b in in_use),
          f"a device holds less than one replica's {param_bytes} "
          f"param bytes: {in_use}")
    for r, tr in enumerate(pools["edge"].transports):
        held = {d for x in jax.tree.leaves((tr.engine.params,
                                            tr.engine.cache))
                for d in x.devices()}
        check(held == {devices[r]},
              f"replica {r} state is on {held}, not {devices[r]}")

    one = {"edge": EnginePool("edge", pools["edge"].transports[:1])}
    t0 = time.perf_counter()
    serve(one, topo, "edge-only", requests)  # compiles
    log(f"setup compile (first 1-replica pass): "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    ref_results = serve(one, topo, "edge-only", requests)
    served_line("serve edge x1", ref_results, time.perf_counter() - t0)
    check_results(ref_results, edge, "edge x1")
    t0 = time.perf_counter()
    serve(pools, topo, "edge-only", requests)  # compiles on chips 1..3
    log(f"setup compile (first 4-replica pass): "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    results = serve(pools, topo, "edge-only", requests)
    served_line("serve edge x4", results, time.perf_counter() - t0)
    check_results(results, edge, "edge x4")
    per_replica = [s["decode_tokens"] for s in pools["edge"].replica_stats()]
    log(f"decode tokens per replica: {per_replica}")
    check(sum(n > 0 for n in per_replica) >= 2,
          f"the router kept every request on one replica: {per_replica}")
    for a, b in zip(ref_results, results):
        check(a.tokens == b.tokens,
              f"rid {a.rid}: 4-replica tokens differ from 1-replica tokens")
    log(f"tokens: {N_REQUESTS}/{N_REQUESTS} requests identical on 4 "
        f"replicas and on 1")
    log(f"peak device memory per device: {peak_bytes(devices)} bytes")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica edge phase (4 chips)")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    entries = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)} | "
        f"jax {jax.__version__} | compile cache {cache} "
        f"({entries} entries at start)")
    configs, sv = published_configs(), serving_config()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(configs, sv)
        else:
            one_chip(configs, sv)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total wall: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
