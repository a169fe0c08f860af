"""Arithmetic several metric readers share: request tails, the work that
fell in the traced window, and device time by kernel or program."""
from __future__ import annotations

import math

from bench import flops
from bench.stats import percentile_rank

# device op names of the kernels, as the TPU trace names them
PAGED_KERNEL = ("decode_attention_paged",)
IMAGE_KERNEL = ("image_stats",)


def tail(run, field: str, q: float):
    """Percentile over every window request; a missing one ranks last and,
    if the tail lands on it, reads as the time it had waited by the end."""
    vals = [getattr(r, field) if r.done else math.inf for r in run.rows]
    if not vals:
        return None
    v = percentile_rank(vals, q)
    if math.isinf(v):
        v = max(run.out.t_end - r.due for r in run.rows if not r.done)
    return v


def tpot_tail(run, q: float):
    """Percentile of (latency - time to first token) / (tokens - 1), in
    milliseconds, over every window request with two or more tokens due.
    A request that failed or had not finished ranks last and, if the tail
    lands on it, reads as the time it had waited by the end spread over the
    tokens after its first."""
    vals, waits = [], []
    for r in run.rows:
        if r.item.max_new < 2:
            continue
        if r.done:
            vals.append((r.latency_s - r.ttft_s) / (len(r.tokens) - 1) * 1e3)
        else:
            vals.append(math.inf)
            waits.append((run.out.t_end - r.due) / (r.item.max_new - 1)
                         * 1e3)
    if not vals:
        return None
    v = percentile_rank(vals, q)
    return max(waits) if math.isinf(v) else v


def in_trace(run, t: float) -> bool:
    lo, hi = run.out.trace_t
    return lo <= t <= hi


def window_flops(run):
    """(prefill, decode) FLOPs of the calls made in the traced window."""
    pre = dec = 0
    for w in run.out.work:
        if w[0] not in ("prefill", "decode") or not in_trace(run, w[1]):
            continue
        m = run.models[w[2]]
        if w[0] == "prefill":
            pre += sum(flops.prefill_flops(m, int(n), w[4]) for n in w[3])
        elif w[0] == "decode":
            for ctx in decode_contexts(w):
                dec += flops.decode_flops(m, ctx)
    return pre, dec


def decode_contexts(w):
    """Context length of every live slot-step of one fused block."""
    _, _, _, positions, budgets, k = w
    for pos, b in zip(positions.tolist(), budgets.tolist()):
        for j in range(min(k, b)):
            yield pos + j + 1


def decode_least_time(run) -> float:
    """Roofline time of the paged attention of the window's decode steps:
    per step, the bytes and operations of every live slot together."""
    total = 0.0
    for w in run.out.work:
        if w[0] != "decode" or not in_trace(run, w[1]):
            continue
        m = run.models[w[2]]
        _, _, _, positions, budgets, k = w
        for j in range(k):
            ctxs = [p + j + 1 for p, b in zip(positions.tolist(),
                                              budgets.tolist()) if j < b]
            if not ctxs:
                continue
            nbytes = sum(flops.kv_bytes(m, c) for c in ctxs)
            ops = sum(flops.decode_attn_flops(m, c) for c in ctxs)
            total += flops.least_time(ops, nbytes, run.peak)
    return total


def _match(name: str, pats) -> bool:
    return any(p in name for p in pats)


def op_time(run, pats):
    """(count, seconds) of the device ops whose names match ``pats``."""
    tr = run.out.trace
    if tr is None:
        return 0, 0.0
    n = t = 0
    for name, (c, s) in tr["ops"].items():
        if _match(name, pats):
            n += c
            t += s
    return n, t


def program_time(run, pat: str):
    """(count, seconds) of jitted program executions whose name holds
    ``pat``."""
    tr = run.out.trace
    if tr is None:
        return 0, 0.0
    n = t = 0
    for name, (c, s) in tr["modules"].items():
        if pat in name:
            n += c
            t += s
    return n, t


def decode_block_ms(run):
    """Mean device time of one fused decode block (the engines' jitted
    ``fused`` program) in the traced window, in milliseconds."""
    n, t = program_time(run, "fused")
    return t / n * 1e3 if n else None


def step_mfu(run):
    """Model FLOPs of the prefill and decode calls in the traced window over
    (window x bf16 peak), in percent."""
    if run.out.trace is None:
        return None
    pre, dec = window_flops(run)
    return 100.0 * (pre + dec) / (run.out.trace["window_s"]
                                  * run.peak["bf16_flops"])


def paged_decode_roofline(run):
    """Least time of the traced window's paged decode attention over the
    paged kernel's summed device time, in percent."""
    n, t = op_time(run, PAGED_KERNEL)
    if not n or t <= 0:
        return None
    return 100.0 * decode_least_time(run) / t
