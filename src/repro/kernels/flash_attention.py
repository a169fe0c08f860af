"""Blocked online-softmax (flash) attention Pallas TPU kernel — GQA-aware.

Grid (B, H, nQ, nK) with the KV dimension innermost: the TPU grid executes
sequentially per core, so the running (m, l, acc) state lives in VMEM scratch
and persists across the nK steps of one (b, h, iq) row; the output block is
written once on the last KV step. GQA is expressed in the K/V index_maps
(query head h reads KV head h // group_size) so KV blocks are fetched once
per group, not per query head.

The wrapper moves the head axis ahead of the sequence axis, so every block
is a (block, head_dim) tile in its last two dimensions. Block shapes default
to (128, head_dim): 128 is MXU/VREG aligned, and head_dim is padded to a
lane multiple by the wrapper in ops.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import auto_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int], n_k: int,
            block_q: int, block_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal block skip: block fully above the diagonal contributes nothing
    q_start = iq * block_q
    k_start = ik * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= cols <= rows
        if window is not None:
            ok &= (rows - cols) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,S,H,hd); k/v: (B,S,K,hd). Self-attention (pos == index).

    ``interpret=None`` resolves per-backend (compiled on TPU, interpreted
    elsewhere) so direct callers get the fast mode by default off-CPU.
    """
    if interpret is None:
        interpret = auto_interpret()
    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_q, n_k = s // block_q, s // block_k
    scale = hd ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, n_k=n_k,
        block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))  # (B, heads, S, hd)
    return out.transpose(0, 2, 1, 3)
