"""End-to-end LIVE cluster benchmark: the unified runtime's LiveBackend
under an open-loop arrival process.

Drives ``ClusterServer`` (real reduced-model ``TierEngine`` per tier, the
real MoA-Off scheduler, modeled WAN links, executed partial offload,
EDF admission, optional hedging/fault injection) at a configurable request
rate and reports, per policy:

* p50 / p95 end-to-end latency and mean TTFT (streamed first token),
* goodput (SLO-met completions per second) vs. raw throughput,
* frac_local (fully-local routing fraction), hedge/retry/truncation rates,
* aggregate engine decode tokens/s.

It also runs a **hedge-path migration comparison** on the twin-edge
topology: the same speculative-hedged long-prompt workload, once with
re-prefilling clones (baseline) and once with cross-tier KV migration
(hedged in-service stragglers ship their slot and the donor is retired) —
reporting p50/p95 and the receiving tiers' prefill-token deltas, which
prove migrated requests never prefill twice.

A **chaos comparison** runs the same burst under a deterministic fault
storm (crashed edge tier, throttled twin, degraded cloud uplink) with the
resilience layer off vs on — tier health + circuit breaking must convert
terminal failures into degraded-but-on-time completions (goodput gain).

A **byzantine soak** replays the hedged-migration burst under a
whole-run wire storm (slot-payload corruption, event-stream
drop/dup/reorder) with the invariant auditor on — checksums + the
exactly-once delivery ledger must keep the run auditor-clean at goodput
parity with honest wires, while the wire counters prove the faults fired.

A **scale-out storm** sweeps open-loop arrival rates (Poisson, plus bursty
and diurnal patterns at the knee) against replicated edge engine pools
(R=1 vs R=2, local transport), per policy — the saturation curves
(goodput-at-SLO and p95 vs rate) that show R=2 pushing the knee out and
MoA-Off beating the static baselines past the single-replica knee.

This is the first end-to-end live-cluster number in the perf trajectory —
the serving bench (``serving_bench.py``) measures one engine's hot path;
this one measures the whole control plane. Emits ``BENCH_cluster.json`` at
the repo root (CI uploads it as an artifact; ``--smoke`` shrinks the grid).

    PYTHONPATH=src python benchmarks/cluster_bench.py            # full
    PYTHONPATH=src python benchmarks/cluster_bench.py --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax
import numpy as np

from repro.config import TOPOLOGIES, ServingConfig, get_topology
from repro.core.baselines import make_policy
from repro.core.scheduler import MoAOffScheduler
from repro.data.synthetic import make_image, make_text_meta
from repro.serving.tiers import (ClusterServer, build_cluster_engines,
                                 build_engine_pools)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO_ROOT, "BENCH_cluster.json")


def make_workload(n: int, rate: float, seed: int, hw: int = 48):
    """(delay_s, text, image) tuples from a Poisson arrival process whose
    content difficulty sweeps the scorer's range (same latent-knob scheme
    as the simulator's RequestGenerator, with real payloads)."""
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for i in range(n):
        t += rng.exponential(1.0 / rate)
        u = float(rng.beta(1.6, 1.6))
        if i % 4 == 3:
            # easy image + entity-dense long text: routes the text out while
            # the image stays local -> exercises executed partial offload
            # (the routed tier encodes, embeddings ship to the fusion tier)
            u = 0.05
            text = (f"Request {i}: compare Table {i} against Report "
                    f"{i + 1} and Figure {i + 2}. " * 10)
        else:
            meta = make_text_meta(rng, float(rng.beta(1.4, 2.2)))
            words = max(4, min(60, meta["tokens"] // 4))
            text = (f"Request {i}: describe the Scene. "
                    + "and explain why the Detail matters. " * (words // 6))
        out.append((t, text, make_image(rng, u, hw, hw)))
    return out


def run_policy(policy: str, topo, sv: ServingConfig, workload, args) -> dict:
    topo = get_topology(topo) if isinstance(topo, str) else topo
    server = ClusterServer(
        build_cluster_engines(topo, sv), topology=topo,
        scheduler=MoAOffScheduler(policy=make_policy(policy, topology=topo)),
        hedge_after_s=args.hedge_after, fail_rate=args.fail_rate)
    # warmup: drive all-local, all-remote and split requests over several
    # prompt lengths with a long decode, so every engine's prefill buckets,
    # context-bucket ladder and encode paths compile before timing
    wrng = np.random.default_rng(1)
    for cx in ({"image": 0.05, "text": 0.05}, {"image": 0.95, "text": 0.95},
               {"image": 0.05, "text": 0.95}):
        for words in (3, 12, 24):
            server.submit("warm up the Compiler please. " * words,
                          image=make_image(wrng, 0.5, 48, 48),
                          max_new=max(args.max_new, 16), complexity=cx)
    server.run(timeout_s=args.timeout)
    n_warm = len(server.results)
    # warmup latencies are compile-dominated; don't let them poison the
    # adaptive-τ controller or the EWMA state for the timed run
    server.scheduler = MoAOffScheduler(
        policy=make_policy(policy, topology=topo))
    server.runtime.scheduler = server.scheduler
    tok0 = {t: (e.decode_tokens, e.encode_tokens)
            for t, e in server.engines.items()}
    off0 = server.backend.offloaded_encodes

    for delay, text, img in workload:
        server.submit(text, image=img, max_new=args.max_new,
                      slo_s=args.slo, delay_s=delay)
    t0 = time.perf_counter()
    results = server.run(timeout_s=args.timeout)[n_warm:]
    wall = time.perf_counter() - t0
    lats = np.array([r.latency_s for r in results])
    local = {t.name for t in topo.local_tiers}
    frac_local = float(np.mean([
        all(t in local for t in r.routes.values()) for r in results]))
    dec = sum(e.decode_tokens - tok0[t][0]
              for t, e in server.engines.items())
    enc = sum(e.encode_tokens - tok0[t][1]
              for t, e in server.engines.items())
    return {
        "n": len(results),
        "wall_s": wall,
        "p50_latency_s": float(np.percentile(lats, 50)),
        "p95_latency_s": float(np.percentile(lats, 95)),
        "mean_latency_s": float(lats.mean()),
        "mean_ttft_s": float(np.mean([r.ttft_s for r in results])),
        "goodput_rps": sum(r.on_time for r in results) / wall,
        "throughput_rps": len(results) / wall,
        "frac_local": frac_local,
        "hedged": float(np.mean([r.hedged for r in results])),
        "retries": float(np.mean([r.retries for r in results])),
        "truncated": float(np.mean([r.truncated for r in results])),
        "decode_tok_s": dec / wall,
        "encode_tokens": enc,  # frontend patch tokens encoded (any tier)
        # images genuinely encoded AWAY from their fusion tier — the
        # executed-partial-offload count
        "offloaded_encodes": server.backend.offloaded_encodes - off0,
    }


def run_hedge_migration(args) -> dict:
    """The hedge path with and without migration, on edge-edge-cloud
    (edge/edge1 serve the SAME model -> migration-compatible).

    Both modes run the SAME speculative hedging policy (queued requests AND
    mid-decode stragglers are hedged after ``hedge_after``); the ONLY
    difference is the clone mechanism — baseline clones re-prefill from
    token 0 and race their donor, migrated clones receive the donor's cache
    rows over the wire and retire it (preemption stays off so the delta is
    attributable to the hedge path alone). The receiving tiers'
    prefill-token deltas prove migrated work is never prefilled twice."""
    from repro.config import PolicyConfig

    topo = get_topology("edge-edge-cloud")
    n = 4 if args.smoke else 6
    sv = ServingConfig(max_batch=n, max_seq=256)
    # a tight burst of uniform long-prompt, long-decode requests: everyone
    # is admitted (no queue) and still decoding when the hedge fires, so
    # EVERY hedge is an in-service backup — the path migration changes
    workload = [(0.05 * i, f"Request {i}: summarize the Report. "
                 + "and weigh every Detail carefully. " * 12)
                for i in range(n)]
    out = {}
    for mode in ("baseline", "migrate"):
        server = ClusterServer(
            build_cluster_engines(topo, sv), topology=topo,
            scheduler=MoAOffScheduler(policy=make_policy(
                "moa-off", PolicyConfig(adaptive_tau=False), topology=topo)),
            hedge_after_s=0.5, hedge_in_service=True,
            migrate=(mode == "migrate"))
        # warm every engine out-of-band: the fused-decode context ladder up
        # to max_seq AND every (length-bucket, row-count) prefill trace the
        # burst can hit, so the timed region measures serving, not XLA
        for i, (tier, eng) in enumerate(server.engines.items()):
            rid = 90_000 + 1_000 * i
            for rows in (1, 2, n):
                for r in range(rows):
                    eng.submit(rid, (np.arange(100) % 300 + 4)
                               .astype(np.int32), max_new=4)
                    rid += 1
                eng.run_until_drained()
            eng.submit(rid, (np.arange(128) % 300 + 4).astype(np.int32),
                       max_new=120)  # context ladder through max_seq
            eng.run_until_drained()
        prefill0 = {t_: e.prefill_tokens for t_, e in server.engines.items()}
        for delay, text in workload:
            # pinned local: the whole burst decodes on the edge tier and
            # straggles into the hedge window together
            server.submit(text, max_new=96, slo_s=args.slo, delay_s=delay,
                          complexity={"text": 0.05})
        results = server.run(timeout_s=args.timeout)
        lats = np.array([r.latency_s for r in results])
        out[mode] = {
            "n": len(results),
            "p50_latency_s": float(np.percentile(lats, 50)),
            "p95_latency_s": float(np.percentile(lats, 95)),
            "mean_latency_s": float(lats.mean()),
            "hedged": float(np.mean([r.hedged for r in results])),
            "migrated": float(np.mean([r.migrated for r in results])),
            "migrations": server.runtime.migrations,
            "migration_mb": float(sum(r.migration_bytes
                                      for r in results) / 1e6),
            # prefill tokens spent per tier during the timed run: with
            # migration the receiving twin decodes shipped slots instead of
            # re-prefilling them
            "prefill_tokens": {t_: e.prefill_tokens - prefill0[t_]
                               for t_, e in server.engines.items()},
        }
        print(f"  [hedge/{mode}] p50={out[mode]['p50_latency_s']:.3f}s "
              f"p95={out[mode]['p95_latency_s']:.3f}s "
              f"hedged={out[mode]['hedged']:.2f} "
              f"migrations={out[mode]['migrations']} "
              f"prefill={out[mode]['prefill_tokens']}", flush=True)
    return out


def run_speculative(args) -> dict:
    """Cross-tier speculative decoding vs plain offloaded decode on the
    two-tier pair, across WAN bandwidths.

    The same cloud-fused long-decode burst runs twice per bandwidth — once
    decoding every token on the cloud engine (plain offload) and once with
    the edge engine drafting k-token blocks the cloud verifies in ONE
    batched decode step (accepted prefixes commit k-at-a-time, the first
    mismatch rolls back). Output tokens are identical by construction
    (greedy verify == target-only decode); the reported delta is committed
    tokens/s and end-to-end latency.

    The stock reduced pair is size-degenerate (both tiers ~0.5 ms/step), so
    this scenario rebuilds the cloud engine at a deeper/wider reduction,
    restoring a steep draft<<target per-step asymmetry (~80x) in place of
    the paper's 2B-vs-7B pair. That is the regime speculation targets: one
    k+1-token verify forward streams the target's weights ONCE where plain
    offload streams them k+1 times."""
    from repro.config import PolicyConfig, SpecConfig, two_tier_topology
    from repro.serving.pool import tier_model_configs
    from repro.serving.tiers import build_cluster_engines

    n = 3 if args.smoke else 6
    max_new = 16 if args.smoke else 48
    k = 12
    bands = [20e6] if args.smoke else [20e6, 300e6]
    # single-stream latency scenario (the regime speculation targets):
    # max_batch=1 so both modes decode one request at a time
    sv = ServingConfig(max_batch=1, max_seq=256)

    def engines_for(topo):
        cfgs = tier_model_configs(topo)
        cfgs["cloud"] = cfgs["cloud"].replace(
            num_layers=12, d_model=384, num_heads=8, num_kv_heads=4,
            d_ff=1536)
        return build_cluster_engines(topo, sv, configs=cfgs)
    workload = [(0.05 * i, f"Request {i}: summarize the Report. "
                 + "and weigh every Detail carefully. " * 10)
                for i in range(n)]
    out = {"draft_k": k, "bands": {}}
    for bw in bands:
        topo = two_tier_topology(bandwidth_bps=bw)
        per = {}
        for mode in ("offload", "speculative"):
            spec = (SpecConfig(draft_tier="edge", target_tier="cloud",
                               draft_k=k) if mode == "speculative" else None)
            server = ClusterServer(
                engines_for(topo), topology=topo,
                scheduler=MoAOffScheduler(policy=make_policy(
                    "moa-off", PolicyConfig(adaptive_tau=False),
                    topology=topo)),
                spec=spec)
            # warmup out-of-band: same shape as the burst, so the draft
            # scan / k+1-verify / re-feed traces all compile before timing
            server.submit("warm up the Compiler please. " * 12,
                          max_new=max_new, complexity={"text": 0.95})
            server.run(timeout_s=args.timeout)
            n_warm = len(server.results)
            for delay, text in workload:
                server.submit(text, max_new=max_new, slo_s=args.slo,
                              delay_s=delay, complexity={"text": 0.95})
            t0 = time.perf_counter()
            results = server.run(timeout_s=args.timeout)[n_warm:]
            wall = time.perf_counter() - t0
            rids = {r.rid for r in results}
            outs = [o for o in server.runtime.outcomes if o.rid in rids]
            drafted = sum(o.drafted_tokens for o in outs)
            accepted = sum(o.accepted_tokens for o in outs)
            lats = np.array([r.latency_s for r in results])
            toks = sum(len(r.tokens) for r in results)
            per[mode] = {
                "n": len(results),
                "wall_s": wall,
                "p50_latency_s": float(np.percentile(lats, 50)),
                "p95_latency_s": float(np.percentile(lats, 95)),
                "mean_ttft_s": float(np.mean([r.ttft_s for r in results])),
                "tok_s": toks / wall,  # committed output tokens/s
                "drafted_tokens": drafted,
                "accepted_tokens": accepted,
                "accept_rate": accepted / drafted if drafted else 0.0,
                "tokens": [r.tokens for r in sorted(results,
                                                    key=lambda r: r.rid)],
            }
            print(f"  [spec/{mode} @ {bw / 1e6:.0f}Mbps] "
                  f"p50={per[mode]['p50_latency_s']:.3f}s "
                  f"p95={per[mode]['p95_latency_s']:.3f}s "
                  f"tok/s={per[mode]['tok_s']:.1f} "
                  f"accept={per[mode]['accept_rate']:.0%}", flush=True)
        # greedy verify commits exactly the target-only stream: same tokens
        parity = per["offload"]["tokens"] == per["speculative"]["tokens"]
        for mode in per:
            del per[mode]["tokens"]
        per["token_parity"] = parity
        per["speedup_tok_s"] = (per["speculative"]["tok_s"]
                                / max(per["offload"]["tok_s"], 1e-9))
        print(f"  [spec @ {bw / 1e6:.0f}Mbps] speculative/offload tok/s = "
              f"{per['speedup_tok_s']:.2f}x | token parity: {parity}",
              flush=True)
        out["bands"][f"{bw / 1e6:.0f}Mbps"] = per
    return out


def run_sessions(args) -> dict:
    """Multi-turn chat through the WHOLE control plane: N sessions x T
    turns (shared system prompt) on the two-tier cluster, with sessions +
    prefix cache ON vs the sessionless replay (every turn re-submits and
    re-prefills its full history). Reports per-turn mean TTFT, the warm
    fraction, and the engines' prefill-token counters — the proof that
    only suffixes were prefilled on warm turns."""
    topo = get_topology("edge-cloud")
    n_sessions = 2 if args.smoke else 4
    turns = 3 if args.smoke else 4
    warmup = 2  # compile-warmup sessions (excluded from the stats)
    system = "you are a Helpful assistant; answer with Care. " * 40
    out = {}
    for mode in ("cold", "warm"):
        sv = ServingConfig(
            max_batch=4, max_seq=1024,
            prefix_cache_mb=64.0 if mode == "warm" else 0.0,
            session_cache_mb=64.0 if mode == "warm" else 0.0)
        server = ClusterServer(
            build_cluster_engines(topo, sv), topology=topo,
            scheduler=MoAOffScheduler(policy=make_policy(
                "moa-off", topology=topo)),
            sessions=(mode == "warm"))
        # compile warmup: throwaway sessions with the same turn lengths
        # (session 1 additionally traces the cross-session prefix-hit path)
        for s in range(n_sessions + warmup):
            for turn in range(turns):
                text = (system if turn == 0 else "") + (
                    f"turn {turn}: expand on Topic {s} with Detail. ")
                # submit_turn builds the full-history prompt either way;
                # with the runtime's sessions off this is the sessionless
                # replay — every turn re-prefills the whole conversation
                server.submit_turn(f"chat-{s}", text, max_new=12,
                                   slo_s=args.slo,
                                   complexity={"text": 0.05})
                server.run(timeout_s=args.timeout)
        results = server.results
        timed = results[warmup * turns:]  # warmup sessions excluded
        per_turn = [[] for _ in range(turns)]
        for i, r in enumerate(timed):
            per_turn[i % turns].append(r.ttft_s)
        out[mode] = {
            "n": len(timed),
            "turn_ttft_s": [float(np.mean(t)) for t in per_turn],
            "warm_frac": float(np.mean([bool(r.warm) for r in timed])),
            "warm_tokens": float(sum(r.warm_tokens for r in timed)),
            "prefill_tokens": {t_: e.prefill_tokens
                               for t_, e in server.engines.items()},
        }
        print(f"  [sessions/{mode}] per-turn ttft "
              f"{[f'{v * 1e3:.1f}' for v in out[mode]['turn_ttft_s']]} ms "
              f"warm={out[mode]['warm_frac']:.2f} "
              f"prefill={out[mode]['prefill_tokens']}", flush=True)
    warm_t = np.mean(out["warm"]["turn_ttft_s"][1:])
    cold_t = np.mean(out["cold"]["turn_ttft_s"][1:])
    out["warm_turn_ttft_speedup"] = float(cold_t / max(warm_t, 1e-9))
    out["config"] = {"sessions": n_sessions, "turns": turns,
                     "system_prompt_words": len(system.split())}
    print(f"  [sessions] warm-turn ttft speedup "
          f"{out['warm_turn_ttft_speedup']:.2f}x", flush=True)
    return out


def run_chaos(args) -> dict:
    """Graceful degradation under a deterministic fault storm: the SAME
    burst on edge-edge-cloud with the edge tier crashed for the whole run,
    its twin throttled 2x and the cloud uplink at half bandwidth — once
    with the resilience layer off (bounded retries hammer the dead tier
    until every routed request fails terminally) and once with tier health
    on (the circuit opens after two failures, arrivals and retries re-route
    to the best surviving tier, sheddable work is dropped at the deadline).
    Health-on must win on goodput: the storm is survivable, the baseline
    just doesn't route around it."""
    from repro.config import PolicyConfig, ResilienceConfig
    from repro.serving.faults import FaultEvent, FaultPlan

    topo = get_topology("edge-edge-cloud")
    n = 6 if args.smoke else 12
    sv = ServingConfig(max_batch=4, max_seq=192, heartbeat_timeout_s=0.2)
    plan = FaultPlan([
        FaultEvent("crash", "edge", t=0.0, duration=120.0),
        FaultEvent("slow", "edge1", t=0.0, duration=120.0, magnitude=2.0),
        FaultEvent("degrade", "cloud", t=0.0, duration=120.0, magnitude=0.5),
    ])
    rng = np.random.default_rng(7)
    workload, t = [], 0.0
    for i in range(n):
        t += rng.exponential(1.0 / 4.0)
        workload.append((t, f"Request {i}: describe the Scene. "
                         + "and explain why the Detail matters. " * 4))
    modes = {
        "health_off": None,
        "health_on": ResilienceConfig(
            health=True, quarantine_after=2, probe_after_s=10.0,
            retry_backoff=True, shed=True, transfer_timeout_s=2.0),
    }
    out = {}
    for mode, res_cfg in modes.items():
        server = ClusterServer(
            build_cluster_engines(topo, sv), topology=topo,
            scheduler=MoAOffScheduler(policy=make_policy(
                "moa-off", PolicyConfig(adaptive_tau=False), topology=topo)),
            fault_plan=plan, resilience=res_cfg)
        for i, eng in enumerate(server.engines.values()):  # compile warmup
            eng.submit(90_000 + i, (np.arange(24) % 300 + 4)
                       .astype(np.int32), max_new=24)
            eng.run_until_drained()
        t0 = time.perf_counter()
        for delay, text in workload:
            server.submit(text, max_new=16, slo_s=args.slo, delay_s=delay,
                          complexity={"text": 0.05})
        results = server.run(timeout_s=args.timeout)
        wall = time.perf_counter() - t0
        done = [r for r in results if not r.failed]
        lats = (np.array([r.latency_s for r in done]) if done
                else np.array([float("inf")]))
        health = server.runtime.health
        ok = sum((not r.failed) and r.on_time for r in results)
        out[mode] = {
            "n": len(results),
            "completed": len(done),
            "failed": sum(r.fail_reason == "retries" for r in results),
            "shed": sum(r.fail_reason == "shed" for r in results),
            "degraded": sum(r.degraded for r in results),
            "goodput_frac": ok / max(len(results), 1),
            "goodput_rps": ok / wall,
            # latency percentiles over COMPLETED requests (a terminal
            # failure resolves fast — it must not flatter the percentile)
            "p50_latency_s": float(np.percentile(lats, 50)),
            "p95_latency_s": float(np.percentile(lats, 95)),
            "quarantines": health.quarantine_count if health else 0,
            "restores": server.backend.restores,
        }
        print(f"  [chaos/{mode}] goodput={out[mode]['goodput_frac']:.2f} "
              f"failed={out[mode]['failed']} shed={out[mode]['shed']} "
              f"degraded={out[mode]['degraded']} "
              f"p95={out[mode]['p95_latency_s']:.3f}s "
              f"quarantines={out[mode]['quarantines']}", flush=True)
    out["goodput_gain"] = (out["health_on"]["goodput_frac"]
                           - out["health_off"]["goodput_frac"])
    return out


def run_soak(args) -> dict:
    """Byzantine wire soak: the SAME hedged long-decode burst on
    edge-edge-cloud, once on honest wires and once under a whole-run
    byzantine storm (migration/session payload corruption, plus
    drop/dup/reorder on every replica's sequenced event stream), BOTH
    runs with the invariant auditor on.

    The defense stack must make the storm invisible at the service level:
    every corrupted slot payload is caught by a CRC32 (and the clone
    re-prefills — recovered, never garbage KV), every duplicated frame is
    suppressed by the delivery ledger, every drop/reorder heals via
    outbox resync — so the byzantine run ends auditor-clean with goodput
    within 10% of the honest run, and the wire counters prove the faults
    actually fired."""
    from repro.config import PolicyConfig
    from repro.serving.faults import FaultPlan

    topo = get_topology("edge-edge-cloud")
    n = 4 if args.smoke else 6
    sv = ServingConfig(max_batch=n, max_seq=256)
    # the hedge-migration recipe: a tight burst of uniform long decodes
    # pinned local, in-service hedges after 50 ms (decode outlives the
    # window on any host), clones receive the donor's slot over the (now
    # hostile) migration wire
    workload = [(0.05 * i, f"Request {i}: audit the Ledger. "
                 + "and verify every Invariant still holds. " * 12)
                for i in range(n)]
    storm = FaultPlan.byzantine_storm(seed=args.seed + 1, corrupt=0.9,
                                      dup=0.25, drop=0.15, reorder=0.1)
    out = {}
    for mode in ("fault_free", "byzantine"):
        server = ClusterServer(
            build_cluster_engines(topo, sv), topology=topo,
            scheduler=MoAOffScheduler(policy=make_policy(
                "moa-off", PolicyConfig(adaptive_tau=False), topology=topo)),
            hedge_after_s=0.05, hedge_in_service=True, migrate=True,
            fault_plan=storm if mode == "byzantine" else None,
            audit=True)
        # warm every engine out-of-band (same ladder as the hedge bench)
        for i, (tier, eng) in enumerate(server.engines.items()):
            rid = 80_000 + 1_000 * i
            for rows in (1, 2, n):
                for r in range(rows):
                    eng.submit(rid, (np.arange(100) % 300 + 4)
                               .astype(np.int32), max_new=4)
                    rid += 1
                eng.run_until_drained()
            eng.submit(rid, (np.arange(128) % 300 + 4).astype(np.int32),
                       max_new=120)
            eng.run_until_drained()
        t0 = time.perf_counter()
        for delay, text in workload:
            server.submit(text, max_new=96, slo_s=args.slo, delay_s=delay,
                          complexity={"text": 0.05})
        results = server.run(timeout_s=args.timeout)
        wall = time.perf_counter() - t0
        lats = np.array([r.latency_s for r in results])
        ws = dict(server.runtime.wire_stats)
        verdict = server.runtime.auditor.last
        ok = sum((not r.failed) and r.on_time for r in results)
        out[mode] = {
            "n": len(results),
            "wall_s": wall,
            "goodput_rps": ok / wall,
            "goodput_frac": ok / max(len(results), 1),
            "p50_latency_s": float(np.percentile(lats, 50)),
            "p95_latency_s": float(np.percentile(lats, 95)),
            "hedged": int(sum(r.hedged for r in results)),
            "migrations": server.runtime.migrations,
            "wire": ws,
            "audit_clean": bool(verdict["clean"]),
            "violations": list(verdict["violations"]),
        }
        print(f"  [soak/{mode}] goodput={out[mode]['goodput_frac']:.2f} "
              f"({out[mode]['goodput_rps']:.2f} rps) "
              f"p95={out[mode]['p95_latency_s']:.3f}s "
              f"corrupt={ws.get('corrupt_detected', 0)}"
              f"/{ws.get('corrupt_injected', 0)} "
              f"dups={ws.get('dups_suppressed', 0)} "
              f"resyncs={ws.get('resyncs', 0)} "
              f"audit={'CLEAN' if verdict['clean'] else 'VIOLATIONS'}",
              flush=True)
        if not verdict["clean"]:
            for v in verdict["violations"]:
                print(f"    ! {v}", flush=True)
    byz, ff = out["byzantine"], out["fault_free"]
    out["goodput_ratio"] = (byz["goodput_frac"]
                            / max(ff["goodput_frac"], 1e-9))
    out["storm"] = json.loads(storm.to_json())
    print(f"  [soak] byzantine/fault-free goodput ratio "
          f"{out['goodput_ratio']:.2f} | detected corruptions "
          f"{byz['wire'].get('corrupt_detected', 0)}, suppressed dups "
          f"{byz['wire'].get('dups_suppressed', 0)}", flush=True)
    return out


def make_storm_arrivals(n: int, rate: float, pattern: str,
                        seed: int) -> np.ndarray:
    """Arrival times for one storm cell: ``poisson`` (open-loop exponential
    gaps), ``burst`` (back-to-back clumps at the same mean rate — the
    worst case for a single replica's admission queue), or ``diurnal``
    (thinned inhomogeneous Poisson, rate swinging ±80% sinusoidally)."""
    rng = np.random.default_rng(seed)
    if pattern == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, n))
    if pattern == "burst":
        burst = 4
        times, t = [], 0.0
        while len(times) < n:
            t += rng.exponential(burst / rate)
            times.extend(t + 0.005 * j for j in range(burst))
        return np.asarray(times[:n])
    if pattern == "diurnal":
        period = max(4.0, n / rate / 2.0)
        lam_max = rate * 1.8
        times, t = [], 0.0
        while len(times) < n:
            t += rng.exponential(1.0 / lam_max)
            if rng.random() < (1 + 0.8 * np.sin(2 * np.pi * t / period)) / 1.8:
                times.append(t)
        return np.asarray(times)
    raise ValueError(f"unknown arrival pattern {pattern!r}")


def run_storm(args) -> dict:
    """Scale-out saturation curves: goodput-at-SLO and p95 latency vs
    arrival rate, per policy, per edge replica count, on the two-tier
    cluster with replicated engine pools (local transport).

    Two saturating resources bound the static policies: a small
    per-replica batch caps the edge tier's compute (edge-only knees
    first) and a constrained uplink makes every cloud-routed image queue
    on the WAN station (cloud-only knees on bandwidth — the paper's
    offload-overhead regime). R=2 must push the edge knee out
    (materially higher goodput-at-SLO under the same storm), and MoA-Off
    must beat both static policies past the single-replica knee by
    splitting the storm across compute and bandwidth headroom. Bursty
    and diurnal arrival patterns re-run the knee rate — the regimes
    where tier-local load balancing earns its keep."""
    import dataclasses as dc

    topo = get_topology("edge-cloud")
    # constrain the WAN so a remote-routed image costs ~0.15 s of uplink:
    # the cloud's knee is bandwidth (~7 rps), the edge's knee is compute
    topo = dc.replace(topo, tiers=tuple(
        dc.replace(t, uplink_bps=250e3) if t.is_remote else t
        for t in topo.tiers))
    sv = ServingConfig(max_batch=2, max_seq=128)
    slo_s = 2.5
    img_hw = 96  # 96x96 -> ~4.6 KB payload -> ~0.15 s on the 250 kbps WAN
    # decode-dominated requests + a step throttle emulate weak edge
    # silicon: a single replica serves ~9 rps, so the edge knee sits
    # between the 8 and 16 rps rate points (and R=2 pushes it past 16).
    # Past the edge knee MoA-Off splits the storm: the sub-tau share stays
    # within edge capacity (below the Eq. 5 load gate) while the overflow
    # rides the WAN headroom — beating either static policy alone
    max_new = 48
    edge_throttle = 4.0
    # full curves run process replicas (each tier decodes in its own
    # worker, so a saturated cloud cannot head-of-line block edge decode
    # through the shared advance loop); --smoke keeps the in-process
    # local transport for a cheap deterministic CI liveness lane
    transport = "local" if args.smoke else "process"
    if args.smoke:
        rates, policies = [4.0, 16.0], ["moa-off", "cloud-only"]
        reps, patterns = [1, 2], ["poisson"]
        n_for = lambda rate: 6  # noqa: E731
    else:
        rates = [2.0, 4.0, 8.0, 16.0]
        policies = ["moa-off", "edge-only", "cloud-only"]
        reps, patterns = [1, 2], ["poisson", "burst", "diurnal"]
        n_for = lambda rate: int(min(96, max(16, rate * 8)))  # noqa: E731
    knee_rate = rates[-1]
    curves = []
    for n_rep in reps:
        pools = build_engine_pools(topo, sv,
                                   replicas={"edge": n_rep, "cloud": 1},
                                   transport=transport)
        # compile warmup once per replica set, with STORM-SHAPED prompts:
        # prefill buckets compile per prompt-length bucket, so the warmup
        # must sweep the same words cycle the timed cells use (routed to
        # both tiers via the complexity extremes)
        wsrv = ClusterServer(pools, topology=topo, scheduler=MoAOffScheduler(
            policy=make_policy("moa-off", topology=topo)))
        wrng0 = np.random.default_rng(1)
        for cx in (0.05, 0.95):
            for words in (4, 12, 24):
                for _ in range(n_rep):
                    wsrv.submit("Request 0: describe the Scene. "
                                + "and explain why the Detail matters. "
                                * words,
                                image=make_image(wrng0, 0.5, img_hw, img_hw),
                                max_new=max_new,
                                complexity={"image": cx, "text": cx})
        wsrv.run(timeout_s=args.timeout)
        # throttle AFTER warmup: the sleep multiplies real step durations,
        # so throttling the (seconds-long) compile steps would stall the
        # first timed cell for minutes
        for repl in pools["edge"].transports:
            repl.set_throttle(edge_throttle)
        for pattern in patterns:
            cell_rates = rates if pattern == "poisson" else [knee_rate]
            for rate in cell_rates:
                n = n_for(rate)
                arrivals = make_storm_arrivals(n, rate, pattern, args.seed)
                wrng = np.random.default_rng(args.seed + 1)
                for pol in policies:
                    server = ClusterServer(
                        pools, topology=topo,
                        scheduler=MoAOffScheduler(
                            policy=make_policy(pol, topology=topo)))
                    t0 = time.perf_counter()
                    for i, t_arr in enumerate(arrivals):
                        words = (4, 12, 24)[i % 3]
                        u = float(wrng.beta(1.6, 1.6))
                        server.submit(
                            f"Request {i}: describe the Scene. "
                            + "and explain why the Detail matters. " * words,
                            image=make_image(wrng, u, img_hw, img_hw),
                            max_new=max_new, slo_s=slo_s,
                            delay_s=float(t_arr),
                            complexity={"image": u, "text": u})
                    results = server.run(timeout_s=args.timeout)
                    wall = time.perf_counter() - t0
                    lats = np.array([r.latency_s for r in results])
                    on_time = sum(r.on_time and not r.failed
                                  for r in results)
                    cell = {
                        "policy": pol, "replicas": n_rep, "rate": rate,
                        "pattern": pattern, "n": len(results),
                        "wall_s": wall,
                        "goodput_rps": on_time / wall,
                        "goodput_frac": on_time / max(len(results), 1),
                        "p50_latency_s": float(np.percentile(lats, 50)),
                        "p95_latency_s": float(np.percentile(lats, 95)),
                        "mean_ttft_s": float(np.mean(
                            [r.ttft_s for r in results])),
                        "frac_edge": float(np.mean(
                            [r.tier == "edge" for r in results])),
                    }
                    curves.append(cell)
                    print(f"  [storm {pattern} R={n_rep} rate={rate:g} "
                          f"{pol}] goodput={cell['goodput_rps']:.2f} rps "
                          f"({cell['goodput_frac']:.2f}) "
                          f"p95={cell['p95_latency_s']:.3f}s "
                          f"edge={cell['frac_edge']:.2f}", flush=True)
        for pool in pools.values():
            pool.close()

    def cell(pol, n_rep, rate, pattern="poisson"):
        for c in curves:
            if (c["policy"], c["replicas"], c["rate"],
                    c["pattern"]) == (pol, n_rep, rate, pattern):
                return c
        return None

    # summary: the acceptance deltas the curves must show
    k1, k2 = cell("moa-off", 1, knee_rate), cell("moa-off", 2, knee_rate)
    e1, e2 = cell("edge-only", 1, knee_rate), cell("edge-only", 2, knee_rate)
    base = [cell(p, 1, knee_rate)
            for p in policies if p != "moa-off"]
    summary = {
        "slo_s": slo_s, "knee_rate": knee_rate,
        "r1_goodput_at_knee": k1["goodput_rps"] if k1 else None,
        "r2_goodput_at_knee": k2["goodput_rps"] if k2 else None,
        "r2_over_r1": (k2["goodput_rps"] / max(k1["goodput_rps"], 1e-9)
                       if k1 and k2 else None),
        "r2_over_r1_edge_only": (
            e2["goodput_rps"] / max(e1["goodput_rps"], 1e-9)
            if e1 and e2 else None),
        "moa_off_vs_best_static_at_knee": (
            k1["goodput_rps"] / max(max(c["goodput_rps"] for c in base
                                        if c), 1e-9)
            if k1 and any(base) else None),
    }
    print(f"  [storm] R=2/R=1 goodput at {knee_rate:g} rps: "
          f"{summary['r2_over_r1']:.2f}x | moa-off vs best static: "
          f"{summary['moa_off_vs_best_static_at_knee']:.2f}x", flush=True)
    return {"curves": curves, "summary": summary,
            "config": {"rates": rates, "policies": policies,
                       "replicas": reps, "patterns": patterns,
                       "max_batch": sv.max_batch, "slo_s": slo_s,
                       "transport": transport}}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--slo", type=float, default=5.0)
    ap.add_argument("--hedge-after", type=float, default=0.0)
    ap.add_argument("--fail-rate", type=float, default=0.0)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--topology", default="edge-cloud",
                    choices=sorted(TOPOLOGIES))
    ap.add_argument("--policies", nargs="*",
                    default=["moa-off", "edge-only", "cloud-only"])
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny workload, two policies")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    if args.smoke:
        args.requests = 6
        args.arrival_rate = 8.0
        args.max_new = 4
        args.policies = ["moa-off", "cloud-only"]

    sv = ServingConfig(max_batch=args.max_batch, max_seq=args.max_seq)
    workload = make_workload(args.requests, args.arrival_rate, args.seed)
    results = {}
    for pol in args.policies:
        print(f"[{pol}] serving {args.requests} requests at "
              f"{args.arrival_rate:.1f} req/s on {args.topology}…",
              flush=True)
        m = run_policy(pol, args.topology, sv, workload, args)
        results[pol] = m
        print(f"  p50={m['p50_latency_s']:.3f}s p95={m['p95_latency_s']:.3f}s"
              f" ttft={m['mean_ttft_s']:.3f}s goodput={m['goodput_rps']:.2f}"
              f" rps frac_local={m['frac_local']:.2f}"
              f" decode={m['decode_tok_s']:.1f} tok/s", flush=True)

    print("[speculative] edge-drafted cloud-verified decoding vs plain "
          "offload across WAN bandwidths on edge-cloud…", flush=True)
    results["speculative"] = run_speculative(args)

    print("[hedge migration] re-prefill clones vs cross-tier KV migration "
          "on edge-edge-cloud…", flush=True)
    results["hedge_migration"] = run_hedge_migration(args)

    print("[sessions] multi-turn chat with prefix & session KV reuse vs "
          "sessionless replay on edge-cloud…", flush=True)
    results["multiturn_sessions"] = run_sessions(args)

    print("[chaos] deterministic fault storm, resilience layer off vs on, "
          "on edge-edge-cloud…", flush=True)
    results["chaos"] = run_chaos(args)

    print("[soak] byzantine wire storm (corrupt/drop/dup/reorder) with "
          "exactly-once delivery and the invariant auditor on "
          "edge-edge-cloud…", flush=True)
    results["soak"] = run_soak(args)

    print("[storm] scale-out saturation curves (replicated edge pool, "
          "poisson/burst/diurnal arrivals) on edge-cloud…", flush=True)
    results["storm"] = run_storm(args)

    payload = {
        "bench": "cluster_live",
        "meta": {
            "platform": platform.platform(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "device": str(jax.devices()[0]),
            "topology": args.topology,
            "requests": args.requests,
            "arrival_rate": args.arrival_rate,
            "max_new": args.max_new,
            "max_batch": args.max_batch,
            "slo_s": args.slo,
            "hedge_after_s": args.hedge_after,
            "fail_rate": args.fail_rate,
            "smoke": args.smoke,
        },
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
