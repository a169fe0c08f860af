"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Grid (B, nT): KV-length blocks innermost. Each grid step streams one
(block_t, K, hd) block holding every KV head, so the block's last two
dimensions are the array's own (K, hd) and any K tiles on the chip; the
G query heads of each KV head keep their running (m, l, acc) in VMEM
scratch. Ring-buffer caches are handled by masking on ABSOLUTE slot
positions (pos_cache), exactly like the XLA reference — empty slots carry
position -1 and are masked out. The query position rides in SMEM as a
scalar-prefetch operand.

Latency note: decode attention is memory-bound (reads the whole KV cache,
does O(1) FLOPs per byte); the win of the kernel is fusing mask+softmax+
combine into the single streaming pass over HBM.
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


def _kernel(posq_ref, q_ref, k_ref, v_ref, posc_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, window: Optional[int],
            n_t: int, last_ref=None):
    b = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _update():
        pos_q = posq_ref[b]                    # SMEM scalar
        pos_c = posc_ref[0, 0]                 # (1, bt) int32
        d = pos_q - pos_c
        ok = (pos_c >= 0) & (d >= 0)
        if window is not None:
            ok &= d < window

        for j in range(q_ref.shape[1]):        # KV heads (static)
            k = k_ref[0, :, j, :]              # (bt, hd)
            v = v_ref[0, :, j, :]
            q = q_ref[0, j].astype(k.dtype)    # (G, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, NEG_INF)      # (G, bt)
            m_prev = m_ref[j]                  # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            acc_ref[j] = acc_ref[j] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[j] = m_new
            l_ref[j] = l_ref[j] * corr + jnp.sum(p, axis=1, keepdims=True)

    if last_ref is None:
        _update()
    else:
        # past the row's last live block every key is masked, so the update
        # would add p = 0 under corr = 1: skipping it changes no bit
        pl.when(it <= last_ref[b])(_update)

    @pl.when(it == n_t - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel(pages_ref, posq_ref, last_ref, *refs, **kw):
    # the page table is consumed by the k/v index_maps (scalar prefetch);
    # the block body is the dense flash-decode update, up to ``last``
    del pages_ref
    _kernel(posq_ref, *refs, last_ref=last_ref, **kw)


def _call(kernel, prefetch, qg, k, v, posc, kv_block, kv_map, pos_map,
          window, interpret):
    """pallas_call plumbing shared by the dense and paged kernels: grid
    (B, nT) over ``posc``'s (B, nT, 1, bt) position blocks, scalar-prefetch
    operands ``prefetch`` first, the kv blocks placed by ``kv_map`` and the
    position blocks by ``pos_map``."""
    b, kh, g, hd = qg.shape
    n_t = posc.shape[1]
    q_spec = pl.BlockSpec((1, kh, g, hd), lambda b_, it, *_: (b_, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_t),
        in_specs=[
            q_spec,
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec((1, 1, 1, posc.shape[-1]), pos_map),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kh, g, hd), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, scale=hd ** -0.5, window=window, n_t=n_t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, qg, k, v, posc)


def decode_attention_pallas_paged(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, pages: jax.Array,
                                  pos_q: jax.Array, pos_cache: jax.Array, *,
                                  window: Optional[int] = None,
                                  interpret: Optional[bool] = None
                                  ) -> jax.Array:
    """Paged flash-decode: the KV context is gathered page-by-page THROUGH
    the page table, straight out of the shared physical pool.

    q: (B,1,H,hd); k/v_pool: (P, page, K, hd) physical pages; pages: (B, NP)
    int32 page table rows (0 = the engine's null page); pos_q: (B,);
    pos_cache: (B, T<=NP*page) absolute positions per logical row.

    The page table rides in as a scalar-prefetch operand: the kv BlockSpec
    index_map reads ``pages[b, it]`` to pick the PHYSICAL page for grid step
    ``it``, so each page streams from HBM exactly once and no gathered copy
    of the context is ever materialized. Entries past ``pos_cache``'s width
    (the partial last page) and null-page garbage carry pos = -1 and mask to
    an exact zero, like the dense kernel's empty slots.

    Only pages up to each row's live bound are fetched and computed:
    ``last[b]``, the highest logical page holding an entry with
    ``0 <= pos <= pos_q[b]`` (0 if none), is reduced from ``pos_cache``
    itself and prefetched beside the table. Grid steps past it repeat the
    page index of step ``last[b]`` (so no DMA is issued) and skip the
    update. That is exact: every key past the bound is masked, and a fully
    masked page after a live one adds p = exp(NEG_INF - m) = 0 under
    corr = 1, leaving (acc, m, l) bit-identical. Taking the bound from the
    positions, not from a length, keeps it right for any page table:
    shared prefix pages, sliding windows, garbage pages past the frontier.
    A row with no live entry, whose output no caller reads, returns the
    mean of its first page's values (the reference: of every entry's).
    """
    if interpret is None:
        interpret = auto_interpret()
    b, _, h, hd = q.shape
    kh = k_pool.shape[2]
    g = h // kh
    page = k_pool.shape[1]
    n_p = pages.shape[1]
    t = pos_cache.shape[1]
    if t < n_p * page:  # pad the ragged tail; pos -1 masks the pad entries
        pos_cache = jnp.pad(pos_cache, ((0, 0), (0, n_p * page - t)),
                            constant_values=-1)
    qg = q[:, 0].reshape(b, kh, g, hd)
    pos_q = pos_q.astype(jnp.int32)
    posc = pos_cache.astype(jnp.int32).reshape(b, n_p, 1, page)
    live = jnp.any((posc >= 0) & (posc <= pos_q[:, None, None, None]),
                   axis=(2, 3))                                  # (B, NP)
    last = jnp.max(jnp.where(live, jnp.arange(n_p, dtype=jnp.int32), 0),
                   axis=1)
    step = lambda b_, it, lst: jnp.minimum(it, lst[b_])
    out = _call(_paged_kernel, (pages.astype(jnp.int32), pos_q, last),
                qg, k_pool, v_pool, posc, (1, page, kh, hd),
                lambda b_, it, pg, pq, lst:
                    (pg[b_, step(b_, it, lst)], 0, 0, 0),
                lambda b_, it, pg, pq, lst: (b_, step(b_, it, lst), 0, 0),
                window, interpret)
    return out.reshape(b, 1, h, hd)


def decode_attention_pallas(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, pos_q: jax.Array,
                            pos_cache: jax.Array, *,
                            window: Optional[int] = None,
                            block_t: int = 512,
                            interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,1,H,hd); k/v_cache: (B,T,K,hd); pos_q: (B,); pos_cache: (B,T).

    Returns (B,1,H,hd). Ragged cache lengths (t % block_t != 0) are
    handled copy-free when t has a decent power-of-two divisor (e.g. the
    serving engine's 1.5*2^n context buckets) by shrinking block_t to it;
    only pathological lengths fall back to padding a tail block whose
    slots carry pos=-1 (the kernel's empty-slot masking ignores them).
    ``interpret=None`` resolves per-backend (compiled on TPU, interpreted
    elsewhere).
    """
    if interpret is None:
        interpret = auto_interpret()
    b, _, h, hd = q.shape
    t, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    block_t = min(block_t, t)
    if t % block_t:
        # largest power-of-two divisor of t, capped by the requested block
        p2 = t & (-t)
        bt = min(p2, 1 << (block_t.bit_length() - 1))
        if bt >= 128:
            block_t = bt  # divides t exactly: no copy
        else:
            tail = (-t) % block_t
            zpad = [(0, 0)] * 4
            zpad[1] = (0, tail)
            k_cache = jnp.pad(k_cache, zpad)
            v_cache = jnp.pad(v_cache, zpad)
            pos_cache = jnp.pad(pos_cache, ((0, 0), (0, tail)),
                                constant_values=-1)
            t += tail
    n_t = t // block_t

    qg = q[:, 0].reshape(b, kh, g, hd)  # (B,K,G,hd), head h = kh_idx*g + g_idx
    posc = pos_cache.astype(jnp.int32).reshape(b, n_t, 1, block_t)
    block = lambda b_, it, pq: (b_, it, 0, 0)
    out = _call(_kernel, (pos_q.astype(jnp.int32),), qg, k_cache, v_cache,
                posc, (1, block_t, kh, hd), block, block, window, interpret)
    return out.reshape(b, 1, h, hd)
