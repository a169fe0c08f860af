"""Plain reference of the scorer's image complexity (MoA-Off §3.1.1).

Sobel edge density (Eq. 2), gray-level entropy (Eq. 3) and Laplacian
variance (Eq. 4) over the whole image with replicated edges, blended with
the resolution term at the scorer's default weights and calibration
constants, which are the configuration's (``ComplexityConfig`` defaults:
all weights 0.25, reference 1024x1024, edge P5/P95 2/60, Laplacian
P5/P95 10/2500). ``dtype=bfloat16`` computes the per-pixel stencils in
bfloat16 (sums stay float32): the control that a lower precision than the
scorer's float32 must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

W_RES = W_EDGE = W_ENT = W_LAP = 0.25
REF_PIXELS = 1024 * 1024
EDGE_P5, EDGE_P95 = 2.0, 60.0
LAP_P5, LAP_P95 = 10.0, 2500.0
EPS = 1e-6


@functools.partial(jax.jit, static_argnums=(1,))
def _score(img: jax.Array, dtype) -> jax.Array:
    h, w = img.shape
    n = float(h * w)
    p = jnp.pad(img, 1, mode="edge").astype(dtype)
    x = img.astype(dtype)
    gx = (p[:-2, 2:] + 2 * p[1:-1, 2:] + p[2:, 2:]
          - p[:-2, :-2] - 2 * p[1:-1, :-2] - p[2:, :-2])
    gy = (p[2:, :-2] + 2 * p[2:, 1:-1] + p[2:, 2:]
          - p[:-2, :-2] - 2 * p[:-2, 1:-1] - p[:-2, 2:])
    mag = jnp.sqrt(gx * gx + gy * gy).astype(jnp.float32)
    lap = (p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
           - 4 * x).astype(jnp.float32)
    bins = jnp.clip(jnp.floor(x.astype(jnp.float32)), 0, 255).astype(
        jnp.int32)
    hist = jnp.zeros((256,), jnp.float32).at[bins.reshape(-1)].add(1.0)
    c_edge = jnp.clip((jnp.sum(mag) / n - EDGE_P5)
                      / (EDGE_P95 - EDGE_P5 + EPS), 0.0, 1.0)
    lap_mean = jnp.sum(lap) / n
    lap_var = jnp.sum(lap * lap) / n - lap_mean ** 2
    c_lap = jnp.clip((lap_var - LAP_P5) / (LAP_P95 - LAP_P5 + EPS), 0.0,
                     1.0)
    q = hist / n
    c_ent = -jnp.sum(jnp.where(q > 0, q * jnp.log(q), 0.0)) / jnp.log(256.0)
    c_res = min(1.0, n / REF_PIXELS)
    return W_RES * c_res + W_EDGE * c_edge + W_ENT * c_ent + W_LAP * c_lap


def image_score(img: np.ndarray, dtype=jnp.float32) -> float:
    with jax.default_matmul_precision("highest"):
        return float(_score(jnp.asarray(img, jnp.float32), dtype))
