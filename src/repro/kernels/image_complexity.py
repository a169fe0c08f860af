"""Fused image-complexity statistics Pallas TPU kernel (paper §3.1.1).

One pass over each image computes ALL the raw statistics the MoA-Off
modality-aware module needs: Sobel gradient-magnitude sum (edge density,
Eq. 2), Laplacian sum + sum-of-squares (sharpness variance, Eq. 4) and the
256-bin gray histogram (entropy texture, Eq. 3). The GPU version of this
would be OpenCV filters + shared-memory atomic histogram; TPUs have no
atomics, so the histogram is computed as a **bincount-as-GEMM**: a bin id
splits into (high, low) nibbles, and per image row the (16, W) one-hot of
the high nibble is contracted against the (16, W) one-hot of the low nibble
on the MXU, giving that row's 16x16 histogram.

Tiling: grid over the batch; one image per grid step resides in VMEM and
is walked in strips of 8 rows, so every temporary is a few vregs. The
stencils' neighbours come from ``pltpu.roll`` with the image edge clamped
(the ``mode="edge"`` padding of the reference); the strip's halo rows come
from the aligned strips above and below. The wrapper pads each image to a
multiple of (8, 128); padded pixels are masked out of every statistic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import auto_interpret

HIST_BINS = 256
_STRIP = 8      # rows per step: one f32 sublane tile
_NIB = 16       # bins = 16 high nibbles x 16 low nibbles


def _clamped(x, shift, axis, at_edge):
    """``x`` shifted by ``shift`` (+1: next, -1: previous) along ``axis``,
    keeping ``x`` itself where ``at_edge`` (replicate padding)."""
    n = x.shape[axis]
    return jnp.where(at_edge, x, pltpu.roll(x, (-shift) % n, axis))


def _kernel(img_ref, stats_ref, hist_ref, lap_ref, *, h: int, w: int):
    hp, wp = img_ref.shape[1], img_ref.shape[2]
    n_strips = hp // _STRIP
    col = jax.lax.broadcasted_iota(jnp.int32, (_STRIP, wp), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_STRIP, wp), 0)
    nib = jax.lax.broadcasted_iota(jnp.int32, (_NIB, wp), 0).astype(
        jnp.float32)

    def body(i, carry):
        mag_acc, lsq_acc, hist = carry
        r0 = pl.multiple_of(i * _STRIP, _STRIP)
        above = pl.multiple_of(jnp.maximum(r0 - _STRIP, 0), _STRIP)
        below = pl.multiple_of(jnp.minimum(r0 + _STRIP, hp - _STRIP), _STRIP)
        x = img_ref[0, pl.ds(r0, _STRIP), :].astype(jnp.float32)
        x_up = img_ref[0, pl.ds(above, _STRIP), :].astype(jnp.float32)
        x_dn = img_ref[0, pl.ds(below, _STRIP), :].astype(jnp.float32)
        grow = r0 + row
        # rows r-1 / r+1, clamped at the image's first / last row
        up = jnp.where(row == 0, x_up[_STRIP - 1:], pltpu.roll(x, 1, 0))
        up = jnp.where(grow == 0, x, up)
        dn = jnp.where(row == _STRIP - 1, x_dn[:1],
                       pltpu.roll(x, _STRIP - 1, 0))
        dn = jnp.where(grow == h - 1, x, dn)
        first, last = col == 0, col == w - 1
        left = lambda a: _clamped(a, -1, 1, first)
        right = lambda a: _clamped(a, 1, 1, last)
        gx = (right(up) + 2.0 * right(x) + right(dn)
              - left(up) - 2.0 * left(x) - left(dn))
        gy = (left(dn) + 2.0 * dn + right(dn)
              - left(up) - 2.0 * up - right(up))
        lap = up + dn + left(x) + right(x) - 4.0 * x
        valid = (grow < h) & (col < w)
        mag = jnp.where(valid, jnp.sqrt(gx * gx + gy * gy), 0.0)
        lap = jnp.where(valid, lap, 0.0)

        bins = jnp.clip(jnp.floor(x), 0.0, 255.0)
        hi = jnp.where(valid, jnp.floor(bins / _NIB), -1.0)
        lo = bins - _NIB * jnp.floor(bins / _NIB)
        for r in range(_STRIP):
            oh_hi = (hi[r:r + 1] == nib).astype(jnp.bfloat16)  # (16, W)
            oh_lo = (lo[r:r + 1] == nib).astype(jnp.bfloat16)
            hist = hist + jax.lax.dot_general(
                oh_hi, oh_lo, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        lap_ref[pl.ds(r0, _STRIP), :] = lap
        return mag_acc + mag, lsq_acc + lap * lap, hist

    zeros = jnp.zeros((_STRIP, wp), jnp.float32)
    mag, lsq, hist = jax.lax.fori_loop(
        0, n_strips, body,
        (zeros, zeros, jnp.zeros((_NIB, _NIB), jnp.float32)))
    # the Laplacian telescopes to ~0, so its sum is all rounding: reduce
    # the image's whole map at once, as the reference does, not strip by
    # strip nor over the padded lanes
    lap = lap_ref[:, :w]

    total = lambda a: jnp.sum(jnp.sum(a, axis=1, keepdims=True), axis=0,
                              keepdims=True)  # (1, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    stats_ref[0] = jnp.where(lane == 0, total(mag),
                             jnp.where(lane == 1, total(lap),
                                       jnp.where(lane == 2, total(lsq), 0.0)))
    hist_ref[0] = hist


def image_stats_pallas(imgs: jax.Array, *,
                       interpret: Optional[bool] = None):
    """imgs: (B, H, W) float32 in [0,255].

    Returns {"sobel_sum": (B,), "lap_sum": (B,), "lap_sq_sum": (B,),
    "hist": (B, 256)}. ``interpret=None`` resolves per-backend.
    """
    if interpret is None:
        interpret = auto_interpret()
    b, h, w = imgs.shape
    # Mosaic tiles (sublanes, lanes) by (8, 128)
    hp = -(-h // _STRIP) * _STRIP
    wp = -(-w // 128) * 128
    if (hp, wp) != (h, w):
        imgs = jnp.pad(imgs, ((0, 0), (0, hp - h), (0, wp - w)))
    kernel = functools.partial(_kernel, h=h, w=w)
    stats, hist = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, wp), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, _NIB, _NIB), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, 1, 128), jnp.float32),
                   jax.ShapeDtypeStruct((b, _NIB, _NIB), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hp, wp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=3 * hp * wp * 4 + (16 << 20)),
        interpret=interpret,
    )(imgs)
    return {"sobel_sum": stats[:, 0, 0], "lap_sum": stats[:, 0, 1],
            "lap_sq_sum": stats[:, 0, 2], "hist": hist.reshape(b, HIST_BINS)}
