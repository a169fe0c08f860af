"""The one traffic generator: a mix file's parameters -> requests.

Every seed gets the same set of sizes, image contents and arrival gaps,
each set shuffled by the seed: prompt and output lengths are quantiles of
their lognormal, image contents quantiles of their Beta, and the gaps
quantiles of the exponential at the mix's fixed rate, so a run's offered
work does not depend on the seed while the order, the pixels' noise and
the token ids do. The arrivals are open loop: a request is due at its
time whatever the system is doing; the mix names their process
(``poisson`` or ``burst``, see :func:`arrival_times`).

Token ids follow the classes of the program's toy tokenizer (sentence
ends, entity ids, word ids), so its text-complexity score sees sentences
and entities; the share of entities per sentence is the text's latent
content.

The image generator is a copy of the synthetic image of
``repro.data.synthetic.make_image`` (gratings whose frequency and white
noise whose amplitude grow with the content knob, blur below 0.5),
with the blur done by running sums so a megapixel image costs
milliseconds.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

SENT_END_ID = 3
ENTITY_BASE, ENTITY_RANGE = 4, 996
WORD_BASE = 1000


@dataclass
class Item:
    """One request as the traffic defines it."""

    index: int
    due_s: float  # offset from the start of the schedule
    prompt: np.ndarray  # int32 token ids
    max_new: int
    image: Optional[int]  # index into the image pool, or None
    slo_s: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal_set(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the lognormal's quantiles, clipped to the range."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def _beta_set(n: int, a: float, b: float) -> np.ndarray:
    """``n`` values at the Beta(a, b) quantiles (the CDF integrated on a
    fine grid and inverted by interpolation)."""
    grid = np.linspace(0.0, 1.0, 4097)
    mid = (grid[:-1] + grid[1:]) / 2
    pdf = mid ** (a - 1) * (1 - mid) ** (b - 1)
    cdf = np.concatenate([[0.0], np.cumsum(pdf)])
    cdf /= cdf[-1]
    return np.interp(_quantiles(n), cdf, grid)


def _exp_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` gaps at the quantiles of the exponential at this rate."""
    return -np.log1p(-_quantiles(n)) / rate


def arrival_times(arr: dict, n: int, rng: np.random.Generator
                  ) -> np.ndarray:
    """Due times of ``n`` requests at the mix's mean rate ``rate_rps``.

    ``poisson``: exponential gaps. ``burst``: clumps of ``burst_size``
    requests ``burst_spacing_s`` apart, the clumps' starts Poisson at
    ``rate_rps / burst_size`` (the worst case for an admission queue).
    Both draw the same gaps for every seed and only shuffle them, so the
    offered work does not depend on the seed.
    """
    rate, proc = arr["rate_rps"], arr["process"]
    if proc == "poisson":
        gaps = _exp_gaps(n, rate)
        return np.cumsum(rng.permutation(gaps)) - gaps.min()
    if proc == "burst":
        size = int(arr["burst_size"])
        gaps = _exp_gaps(-(-n // size), rate / size)
        starts = np.cumsum(rng.permutation(gaps)) - gaps.min()
        clumps = starts[:, None] + arr["burst_spacing_s"] * np.arange(size)
        return np.sort(clumps.reshape(-1)[:n])  # close clumps overlap
    raise ValueError(f"unknown arrival process {proc!r}")


def request_count(mix: dict, seconds: float) -> int:
    """Requests in the schedule: the ramp plus the window at the rate."""
    arr = mix["arrivals"]
    return max(1, int(round(arr["rate_rps"] * (arr["ramp_s"] + seconds))))


def prompt_ids(rng: np.random.Generator, n: int, content: float,
               text: dict, vocab: int) -> np.ndarray:
    """``n`` token ids: a sentence end every ``sentence_tokens`` on average,
    entities at ``content * entity_max_per_sentence`` per sentence."""
    p_end = 1.0 / text["sentence_tokens"]
    p_ent = min(1.0, content * text["entity_max_per_sentence"]
                / text["sentence_tokens"])
    u = rng.random(n)
    ids = rng.integers(WORD_BASE, vocab, n)
    ent = rng.integers(ENTITY_BASE, ENTITY_BASE + ENTITY_RANGE, n)
    ids = np.where(u < p_end + p_ent, ent, ids)
    ids = np.where(u < p_end, SENT_END_ID, ids)
    return ids.astype(np.int32)


def schedule(mix: dict, seed: int, seconds: float, vocab: int
             ) -> Tuple[List[Item], List[Tuple[Tuple[int, int], float]]]:
    """Requests of one run and the image pool they draw from.

    Returns ``(items, pool)``; ``pool`` lists ``((h, w), content)`` per
    image, the pixels being made by :func:`make_pool`.
    """
    n = request_count(mix, seconds)
    rng = np.random.default_rng(seed)
    due = arrival_times(mix["arrivals"], n, rng)
    prompts = rng.permutation(_lognormal_set(n, mix["prompt_tokens"]))
    outputs = rng.permutation(_lognormal_set(n, mix["output_tokens"]))
    text = mix["text"]
    contents = rng.permutation(_beta_set(n, *text["entity_beta"]))

    pool: List[Tuple[Tuple[int, int], float]] = []
    use = np.full(n, -1)
    img = mix.get("image")
    if img:
        shapes = [tuple(s) for s in img["shapes"]]
        per = img["pool_per_shape"]
        for s in shapes:
            for c in _beta_set(per, *img["content_beta"]):
                pool.append((s, float(c)))
        n_img = int(round(img["share"] * n))
        picks = np.arange(n_img) % len(pool)
        use[:n_img] = picks
        use = rng.permutation(use)
    items = []
    for i in range(n):
        ids = prompt_ids(rng, int(prompts[i]), float(contents[i]), text,
                         vocab)
        items.append(Item(index=i, due_s=float(due[i]), prompt=ids,
                          max_new=int(outputs[i]),
                          image=None if use[i] < 0 else int(use[i]),
                          slo_s=float(mix["slo_s"])))
    return items, pool


def _box_blur(img: np.ndarray, k: int, axis: int) -> np.ndarray:
    """``np.convolve(row, ones(k)/k, mode="same")`` along ``axis``."""
    pad = [(0, 0), (0, 0)]
    pad[axis] = (k // 2 + 1, k // 2)
    c = np.cumsum(np.pad(img, pad), axis=axis, dtype=np.float64)
    n = img.shape[axis]
    hi = np.take(c, np.arange(k, k + n), axis=axis)
    lo = np.take(c, np.arange(0, n), axis=axis)
    return ((hi - lo) / k).astype(np.float32)


def make_image(rng: np.random.Generator, content: float, h: int,
               w: int) -> np.ndarray:
    """One grayscale float32 image in [0, 255]."""
    u = float(np.clip(content, 0.0, 1.0))
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    img = 128.0 + 40.0 * np.sin(2 * np.pi * xx / max(w, 1))
    for k in range(1, 2 + int(6 * u)):
        f = 2.0 ** k
        img += (30.0 * u) * np.sign(np.sin(2 * np.pi * (xx + yy) * f / w))
    img += rng.normal(0.0, 5.0 + 55.0 * u, (h, w)).astype(np.float32)
    if u < 0.5:
        kdim = 1 + 2 * int((0.5 - u) * 8)
        if kdim > 1:
            img = _box_blur(_box_blur(img, kdim, 1), kdim, 0)
    return np.clip(img, 0, 255).astype(np.float32)


def make_pool(pool, seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng([int(seed), 1])
    return [make_image(rng, c, h, w) for (h, w), c in pool]

