"""§3.1 Lightweight Heterogeneous Modality-Aware module.

Image complexity (§3.1.1): weighted sum of resolution / edge-density /
entropy-texture / sharpness indicators, computed by the fused Pallas kernel
(``repro.kernels``) with a pure-jnp fallback oracle.

Text complexity (§3.1.2): token-length + entity-density terms over the toy
tokenizer's token classes.

Audio complexity (beyond-paper extension, same recipe): frame count +
spectral-flux + frame-entropy over precomputed mel frames — lets the MoA-Off
policy route the audio modality of whisper-family requests.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ComplexityConfig


# ---------------------------------------------------------------------------
# image (kernel-backed)
# ---------------------------------------------------------------------------


def image_complexity(imgs: jax.Array,
                     cc: ComplexityConfig = ComplexityConfig(),
                     use_kernel: bool = True,
                     interpret: Optional[bool] = None) -> Dict[str, jax.Array]:
    """imgs: (B, H, W) float32 in [0,255] -> dict incl. ``c_img`` (B,)."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    with obs.span("moa.scheduler.score.stats"):
        if use_kernel:
            stats = kops.image_stats(imgs, interpret=interpret)
        else:
            stats = kref.image_stats_batch_ref(imgs)
    with obs.span("moa.scheduler.score.features"):
        return kops.image_complexity_from_stats(stats, imgs.shape[1],
                                                imgs.shape[2], cc)


def calibrate_percentiles(imgs: jax.Array,
                          cc: ComplexityConfig = ComplexityConfig()
                          ) -> ComplexityConfig:
    """Fit the P5/P95 normalizers (Eq. 2 & 4) on a calibration set."""
    from repro.kernels import ops as kops

    stats = kops.image_stats(imgs)
    n = imgs.shape[1] * imgs.shape[2]
    g = np.asarray(stats["sobel_sum"]) / n
    lm = np.asarray(stats["lap_sum"]) / n
    lv = np.asarray(stats["lap_sq_sum"]) / n - lm ** 2
    import dataclasses

    return dataclasses.replace(
        cc,
        edge_p5=float(np.percentile(g, 5)),
        edge_p95=float(np.percentile(g, 95)),
        lap_p5=float(np.percentile(lv, 5)),
        lap_p95=float(np.percentile(lv, 95)),
    )


# ---------------------------------------------------------------------------
# text
# ---------------------------------------------------------------------------


def text_complexity_from_counts(num_tokens, num_entities, num_sentences,
                                cc: ComplexityConfig = ComplexityConfig()):
    """Vectorized §3.1.2. All args (B,) arrays or scalars."""
    num_tokens = jnp.asarray(num_tokens, jnp.float32)
    num_entities = jnp.asarray(num_entities, jnp.float32)
    num_sentences = jnp.maximum(jnp.asarray(num_sentences, jnp.float32), 1.0)
    c_len = jnp.minimum(1.0, num_tokens / cc.len_l0)
    c_ner = jnp.minimum(1.0, (num_entities / num_sentences) / cc.ner_gamma)
    c_text = cc.beta_len * c_len + cc.beta_ner * c_ner
    return {"c_len": c_len, "c_ner": c_ner, "c_text": c_text}


def text_complexity_from_tokens(tokens: jax.Array, pad_id: int,
                                entity_mask: jax.Array,
                                sentence_end_mask: jax.Array,
                                cc: ComplexityConfig = ComplexityConfig()):
    """tokens (B, L) + per-token class masks -> §3.1.2 scores.

    ``entity_mask``/``sentence_end_mask``: bool (B, L), the toy tokenizer's
    entity/numeral and sentence-terminator classes (stands in for NER).
    """
    valid = tokens != pad_id
    n_tok = jnp.sum(valid, axis=-1)
    n_ent = jnp.sum(entity_mask & valid, axis=-1)
    n_sent = jnp.sum(sentence_end_mask & valid, axis=-1)
    return text_complexity_from_counts(n_tok, n_ent, n_sent, cc)


# ---------------------------------------------------------------------------
# audio (beyond-paper, same single-pass recipe)
# ---------------------------------------------------------------------------


def audio_complexity(frames: jax.Array,
                     cc: ComplexityConfig = ComplexityConfig()):
    """frames: (B, T, F) precomputed mel features -> dict incl ``c_audio``.

    Indicators: duration scale (T/T0), spectral flux (mean |Δframe|,
    squashed), frame-entropy (energy distribution across mel bins).
    """
    frames = frames.astype(jnp.float32)
    b, t, f = frames.shape
    c_dur = jnp.minimum(1.0, t / float(cc.audio_ref_frames))
    flux = jnp.mean(jnp.abs(jnp.diff(frames, axis=1)), axis=(1, 2))
    c_flux = 1.0 - jnp.exp(-flux)
    e = jnp.maximum(frames - frames.min(axis=(1, 2), keepdims=True), 1e-9)
    p = e / jnp.sum(e, axis=2, keepdims=True)
    ent = -jnp.mean(jnp.sum(p * jnp.log(p), axis=2), axis=1) / jnp.log(f)
    c_audio = (c_dur + c_flux + ent) / 3.0
    return {"c_dur": jnp.broadcast_to(c_dur, (b,)), "c_flux": c_flux,
            "c_ent": ent, "c_audio": c_audio}
