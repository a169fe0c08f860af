"""Plain float32 reference of the Qwen2-VL / Qwen2.5-VL decoder as served.

Follows arXiv:2409.12191 and arXiv:2502.13923 for the language model:
pre-norm decoder layers with RMSNorm, grouped-query attention with rotary
position embeddings (rotate-half form, base ``rope_theta``), SwiGLU MLP,
final RMSNorm and an output projection (tied to the embedding for the 2B
model). Departures, each shared with the program under test:

* the vision tower is a stub: the image's pixels, scaled to [0, 1] and
  flattened, are tiled into ``num_patches`` rows of ``frontend_dim`` and
  projected by one matrix into the decoder's width, ahead of the text;
* positions are 1-D (0, 1, 2, ... over image rows then text), not M-RoPE;
* a norm weight ``w`` scales by ``1 + w``;
* the fused MLP projection holds [gate-input; up] side by side, and the
  activation is ``silu(first half) * second half``.

Weights are drawn again from the seed by ``bench/weights.py``, in the
served dtype, by the same jitted draw over the same names and shapes that
made the program's (so the two hold identical values without the
reference reading what the program was given); the float32 computation
then goes layer by layer. Everything runs in float32 under
``jax.default_matmul_precision("highest")``. ``quant="fp8"`` is the
control a lower precision than the configuration's must fail: both
operands of every matrix product are rounded to float8 e4m3, weights with
one scale per output channel and activations with one per row, and the
products accumulate in float32.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F8_MAX = 448.0  # largest finite float8_e4m3fn


def padded_vocab(v: int) -> int:
    return -(-v // 256) * 256


def _fp8(w: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def param_shapes(m: dict) -> dict:
    """The decoder's parameters by name: ``blocks/*`` stacked over layers,
    the fused MLP input ``(d, 2 ff)``, matrices ``(in, out)``, the
    embedding ``(padded vocab, d)``."""
    import jax

    dt = jnp.dtype(m["dtype"])
    d, hd, n = m["d_model"], m["head_dim"], m["num_layers"]
    h, k, ff = m["num_heads"], m["num_kv_heads"], m["d_ff"]
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    tree = {"embed": sd(padded_vocab(m["vocab_size"]), d), "ln_f": sd(d),
            "blocks": {"ln1": sd(n, d), "wq": sd(n, d, h * hd),
                       "wk": sd(n, d, k * hd), "wv": sd(n, d, k * hd),
                       "wo": sd(n, h * hd, d), "ln2": sd(n, d),
                       "mlp": {"wi": sd(n, d, 2 * ff), "wo": sd(n, ff, d)}}}
    if m.get("frontend") == "vision_stub":
        tree["proj_in"] = sd(m["frontend_dim"], d)
    if not m["tie_embeddings"]:
        tree["unembed"] = sd(padded_vocab(m["vocab_size"]), d)
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2))
def _f32(w, quant, axis=-2):
    w = w.astype(jnp.float32)
    if quant == "fp8" and w.ndim >= 2:
        w = _fp8(w, axis)
    return w


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, w, quant):
    """``x @ w``; the control rounds ``x`` too, one scale per row."""
    return (_fp8(x, -1) if quant == "fp8" else x) @ w


def _layer(m: dict, lp: dict, x: jax.Array, quant) -> jax.Array:
    """One decoder layer over one sequence ``x`` (S, d), causal."""
    s = x.shape[0]
    h, k, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pos = jnp.arange(s)
    y = _rms(x, lp["ln1"], m["norm_eps"])
    q = _rope(_mm(y, lp["wq"], quant).reshape(s, h, hd), pos,
              m["rope_theta"])
    kk = _rope(_mm(y, lp["wk"], quant).reshape(s, k, hd), pos,
               m["rope_theta"])
    v = _mm(y, lp["wv"], quant).reshape(s, k, hd)
    g = h // k
    q = q.reshape(s, k, g, hd)
    sc = jnp.einsum("qkgd,skd->kgqs", q, kk) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v).reshape(s, h * hd)
    x = x + _mm(o, lp["wo"], quant)
    y = _rms(x, lp["ln2"], m["norm_eps"])
    a, b = jnp.split(_mm(y, lp["wi"], quant), 2, axis=-1)
    return x + _mm(jax.nn.silu(a) * b, lp["mo"], quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layers_over_batch(mt: tuple, lp: dict, xs: jax.Array, quant
                       ) -> jax.Array:
    m = dict(mt)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda x: _layer(m, lp, x, quant), xs)


@functools.partial(jax.jit, static_argnums=(3, 5))
def _logits(table: jax.Array, h: jax.Array, ln_f: jax.Array, eps: float,
            vocab: int, quant) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        out = _mm(_rms(h, ln_f, eps), table.T, quant)
    return jnp.where(jnp.arange(table.shape[0]) < vocab, out, -jnp.inf)


def patches(image: np.ndarray, num_patches: int, frontend_dim: int
            ) -> np.ndarray:
    """The stub vision tower's input rows for one image."""
    flat = np.asarray(image, np.float32).reshape(-1) / np.float32(255.0)
    need = num_patches * frontend_dim
    rep = max(1, -(-need // max(flat.size, 1)))
    return np.tile(flat, rep)[:need].reshape(num_patches, frontend_dim)


def served_logits(m: dict, key: jax.Array, seqs: List[dict],
                  quant: Optional[str] = None) -> List[np.ndarray]:
    """Logits at the positions that produced the served tokens.

    ``m`` holds the model's sizes as in the configuration file. Each item
    of ``seqs`` has ``prompt`` (int32 ids), ``served`` (the ids served),
    and ``image`` (pixels or None). Returns, per sequence, a float32 array
    ``(len(served), vocab)``: row ``j`` is the distribution the reference
    puts on served token ``j`` given the prompt and served tokens before it.
    """
    d = m["d_model"]
    mt = tuple(sorted((a, b) for a, b in m.items()
                      if isinstance(b, (int, float, str))))
    params = W.make_params(key, param_shapes(m))
    emb = _f32(params["embed"], quant, -1)

    rows, firsts = [], []
    for sq in seqs:
        ids = np.concatenate([sq["prompt"], sq["served"][:-1]]).astype(
            np.int32)
        x = emb[jnp.asarray(ids)]
        if sq.get("image") is not None:
            pin = _f32(params["proj_in"], quant, -2)
            px = patches(sq["image"], m["num_patches"], m["frontend_dim"])
            with jax.default_matmul_precision("highest"):
                x = jnp.concatenate([_mm(jnp.asarray(px), pin, quant), x],
                                    axis=0)
        rows.append(x)
        firsts.append(x.shape[0] - len(sq["served"]))
    if not m["tie_embeddings"]:
        del emb  # the output projection is drawn apart; keep one table
    # pad to few shapes (rows to a power of two, length to 256) so the
    # layer program compiles once for many runs
    s_pad = -(-max(r.shape[0] for r in rows) // 256) * 256
    n_pad = 1 << (len(rows) - 1).bit_length()
    xs = jnp.stack([jnp.pad(r, ((0, s_pad - r.shape[0]), (0, 0)))
                    for r in rows]
                   + [jnp.zeros((s_pad, d), jnp.float32)] * (n_pad - len(rows)))
    blocks = params["blocks"]
    names = {"mo": ("mlp", "wo"), "wi": ("mlp", "wi")}
    for layer in range(m["num_layers"]):
        lp = {}
        for n in ("ln1", "wq", "wk", "wv", "wo", "ln2", "wi", "mo"):
            leaf = blocks
            for part in names.get(n, (n,)):
                leaf = leaf[part]
            lp[n] = _f32(leaf[layer], quant, -2)
        xs = _layers_over_batch(mt, lp, xs, quant)
    ln_f = _f32(params["ln_f"], quant, -2)
    table = emb if m["tie_embeddings"] else _f32(params["unembed"], quant,
                                                  -1)
    del params
    counts = [len(sq["served"]) for sq in seqs]
    hsel = jnp.concatenate([xs[i, f:f + n]
                            for i, (f, n) in enumerate(zip(firsts, counts))])
    n_pad = -(-hsel.shape[0] // 64) * 64
    hsel = jnp.pad(hsel, ((0, n_pad - hsel.shape[0]), (0, 0)))
    logits = np.asarray(_logits(table, hsel, ln_f, m["norm_eps"],
                                m["vocab_size"], quant))
    return np.split(logits[:sum(counts)], np.cumsum(counts)[:-1])
