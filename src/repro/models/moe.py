"""Mixture-of-Experts layer.

Two implementations sharing one param layout:

- ``dense``: every expert computes every token, outputs weighted by the router
  (correctness oracle; used for tiny smoke configs).
- ``dropping``: GShard-style capacity-bounded dispatch implemented with a
  sort-based scatter (NO (T, E, C) one-hot tensor is ever materialized) inside
  an expert-parallel ``shard_map``: tokens stay sharded over the data axis,
  experts are sharded over the model axis, each model shard dispatches the
  local tokens that picked its experts and partial outputs are combined with a
  single psum over the model axis. This is the production path: its working
  set per device is O(E_local * C * D), and the only collective it adds is the
  combine-psum (counted in §Roofline's collective term).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import activation_fn, is_gated
from repro.models.spec import ParamSpec

_shard_map = functools.partial(jax.shard_map, check_vma=False)


def moe_specs(cfg: ModelConfig, prefix_axes=()) -> dict:
    """up ("wi") and gate ("wg") are SEPARATE tensors (not a fused 2F dim):
    fused layouts mis-split when the hidden dim is sharded over the model
    axis (2D expert parallelism / TP of the shared expert)."""
    pshape = tuple(n for n, _ in prefix_axes)
    paxes = tuple(a for _, a in prefix_axes)
    d, fe, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    specs = {
        "router": ParamSpec(pshape + (d, e), paxes + ("embed", "experts_r"), "small"),
        "wi": ParamSpec(pshape + (e, d, fe), paxes + ("experts", "embed", "expert_ffn"), "scaled"),
        "wo": ParamSpec(pshape + (e, fe, d), paxes + ("experts", "expert_ffn", "embed"), "scaled"),
    }
    if is_gated(cfg.activation):
        specs["wg"] = ParamSpec(pshape + (e, d, fe),
                                paxes + ("experts", "embed", "expert_ffn"),
                                "scaled")
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        specs["shared_wi"] = ParamSpec(pshape + (d, fs), paxes + ("embed", "ffn"), "scaled")
        specs["shared_wo"] = ParamSpec(pshape + (fs, d), paxes + ("ffn", "embed"), "scaled")
        if is_gated(cfg.activation):
            specs["shared_wg"] = ParamSpec(pshape + (d, fs),
                                           paxes + ("embed", "ffn"), "scaled")
    return specs


def _router_topk(x: jax.Array, router_w: jax.Array, top_k: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (T, D) -> (ids (T,k), weights (T,k) normalized, probs (T,E))."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)  # norm_topk_prob
    return ids, w, probs


def _aux_loss(probs: jax.Array, ids: jax.Array, num_experts: int) -> jax.Array:
    """Switch/GShard load-balance loss: E * sum_e f_e * p_e."""
    t = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(t * ids.shape[-1], 1)
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def _expert_mlp(xb: jax.Array, wi: jax.Array, wo: jax.Array, activation: str,
                wg=None) -> jax.Array:
    """xb: (E, C, D); wi/wg: (E, D, F); wo: (E, F, D)."""
    up = jnp.einsum("ecd,edf->ecf", xb, wi)
    if is_gated(activation):
        gate = jnp.einsum("ecd,edf->ecf", xb, wg)
        h = activation_fn(activation, up, gate)
    else:
        h = activation_fn(activation, up)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _shared_expert(params: dict, x: jax.Array, activation: str) -> jax.Array:
    up = jnp.einsum("td,df->tf", x, params["shared_wi"])
    if is_gated(activation):
        gate = jnp.einsum("td,df->tf", x, params["shared_wg"])
        h = activation_fn(activation, up, gate)
    else:
        h = activation_fn(activation, up)
    return jnp.einsum("tf,fd->td", h, params["shared_wo"])


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------


def moe_dense_forward(params: dict, x: jax.Array, cfg: ModelConfig
                      ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D). Returns (y, aux_loss). Computes ALL experts (oracle)."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    ids, w, probs = _router_topk(xt, params["router"], cfg.top_k)
    # (T, E) combine weights from top-k selection
    comb = jnp.zeros((xt.shape[0], cfg.num_experts), x.dtype)
    comb = comb.at[jnp.arange(xt.shape[0])[:, None], ids].set(w.astype(x.dtype))
    xb = jnp.broadcast_to(xt[None], (cfg.num_experts,) + xt.shape)
    all_out = _expert_mlp(xb, params["wi"], params["wo"], cfg.activation,
                          params.get("wg"))  # (E, T, D)
    y = jnp.einsum("te,etd->td", comb, all_out)
    if cfg.num_shared_experts:
        y = y + _shared_expert(params, xt, cfg.activation)
    return y.reshape(b, s, d), _aux_loss(probs, ids, cfg.num_experts)


# ---------------------------------------------------------------------------
# dropping (sort-based, expert-parallel)
# ---------------------------------------------------------------------------


def _dispatch_plan(ids: jax.Array, w: jax.Array, e_lo: int, e_local: int,
                   capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Slot-centric dispatch plan (only index/weight vectors — O(T*k) ints,
    never an (T*k, D) tensor).

    ids/w: (T, k). Returns per-SLOT vectors of length E_l*C:
      slot_src  — source token index (0 for empty slots),
      slot_w    — combine weight (0 for empty slots),
      slot_valid— bool mask.
    """
    t, k = ids.shape
    n = t * k
    flat_ids = ids.reshape(-1) - e_lo
    tok_idx = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    w_flat = w.reshape(-1)
    is_local = (flat_ids >= 0) & (flat_ids < e_local)
    sort_key = jnp.where(is_local, flat_ids, e_local)
    order = jnp.argsort(sort_key, stable=True)
    sorted_ids = sort_key[order]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(e_local), side="left")
    pos = jnp.arange(n) - jnp.where(
        sorted_ids < e_local,
        starts[jnp.minimum(sorted_ids, e_local - 1)], 0)
    valid = (sorted_ids < e_local) & (pos < capacity)
    dest = jnp.where(valid, sorted_ids * capacity + pos, e_local * capacity)
    nslots = e_local * capacity
    slot_src = jnp.zeros((nslots + 1,), jnp.int32).at[dest].set(
        tok_idx[order], mode="drop")[:-1]
    slot_w = jnp.zeros((nslots + 1,), w_flat.dtype).at[dest].set(
        jnp.where(valid, w_flat[order], 0.0), mode="drop")[:-1]
    slot_valid = jnp.zeros((nslots + 1,), jnp.bool_).at[dest].set(
        valid, mode="drop")[:-1]
    return slot_src, slot_w, slot_valid


def _dispatch_gather(xt: jax.Array, slot_src: jax.Array,
                     slot_valid: jax.Array, e_local: int, capacity: int
                     ) -> jax.Array:
    """(T, D) tokens -> (E_l, C, D) buffers; empty slots zeroed."""
    x_buf = xt[slot_src] * slot_valid[:, None].astype(xt.dtype)
    return x_buf.reshape(e_local, capacity, -1)


def _combine_scatter(y_buf: jax.Array, slot_src: jax.Array, slot_w: jax.Array,
                     t: int) -> jax.Array:
    """(E_l, C, D) expert outputs -> (T, D) weighted scatter-add."""
    d = y_buf.shape[-1]
    contrib = y_buf.reshape(-1, d) * slot_w[:, None].astype(y_buf.dtype)
    return jnp.zeros((t, d), y_buf.dtype).at[slot_src].add(contrib)


def moe_dropping_local(params: dict, xt: jax.Array, cfg: ModelConfig,
                       model_axis: Optional[str], data_axis) -> Tuple[jax.Array, jax.Array]:
    """Per-shard body (called inside shard_map, or standalone when axes None).

    xt: (T_local, D) tokens local to this data shard, replicated over model.
    Expert weights arrive sliced over the model axis: (E_local, D, F).
    """
    t, d = xt.shape
    e_local = params["wi"].shape[0]
    tp = 1
    e_lo = 0
    if model_axis is not None:
        tp = jax.lax.axis_size(model_axis)
        e_lo = jax.lax.axis_index(model_axis) * e_local
    num_experts = e_local * tp
    ids, w, probs = _router_topk(xt, params["router"], cfg.top_k)
    capacity = max(1, int(t * cfg.top_k / num_experts * cfg.capacity_factor))
    slot_src, slot_w, slot_valid = _dispatch_plan(ids, w, e_lo, e_local,
                                                  capacity)
    x_buf = _dispatch_gather(xt, slot_src, slot_valid, e_local, capacity)
    y_buf = _expert_mlp(x_buf, params["wi"], params["wo"], cfg.activation,
                        params.get("wg"))
    y = _combine_scatter(y_buf, slot_src, slot_w, t)
    if cfg.num_shared_experts:
        # shared expert ffn dim is sharded over model -> partial sums psum below
        y = y + _shared_expert(params, xt, cfg.activation)
        if model_axis is not None:
            # shared ffn slice produced a partial (1/tp) result; psum merges it
            # together with the routed-expert partials in one collective
            pass
    aux = _aux_loss(probs, ids, num_experts)
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    return y, aux


def moe_dropping_forward(params: dict, x: jax.Array, cfg: ModelConfig,
                         mesh, model_axis="model", data_axis="data",
                         batch_axes=("data",)) -> Tuple[jax.Array, jax.Array]:
    """shard_map wrapper: tokens sharded over data (+pod), experts over model."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    pod = ("pod",) if "pod" in mesh.axis_names else ()
    batch_spec = tuple(pod + (data_axis,))

    def body(xt, router, wi, wg, wo, shared):
        p = {"router": router, "wi": wi, "wo": wo}
        if wg is not None:
            p["wg"] = wg
        if shared is not None:
            p.update(shared)
        y, aux = moe_dropping_local(p, xt.reshape(-1, d), cfg, model_axis, None)
        if pod or data_axis:  # average aux over token shards
            y_axes = tuple(a for a in (pod + (data_axis,)) if a)
            aux = jax.lax.pmean(aux, y_axes)
        return y.reshape(xt.shape), aux

    shared = None
    shared_spec = None
    if cfg.num_shared_experts:
        shared = {"shared_wi": params["shared_wi"],
                  "shared_wo": params["shared_wo"]}
        shared_spec = {"shared_wi": P(None, model_axis),
                       "shared_wo": P(model_axis, None)}
        if "shared_wg" in params:
            shared["shared_wg"] = params["shared_wg"]
            shared_spec["shared_wg"] = P(None, model_axis)
    wg = params.get("wg")
    fn = _shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_spec, None, None),          # x
                  P(None, None),                       # router replicated
                  P(model_axis, None, None),           # wi expert-sharded
                  None if wg is None else P(model_axis, None, None),
                  P(model_axis, None, None),           # wo
                  shared_spec,
                  ),
        out_specs=(P(batch_spec, None, None), P()),
    )
    return fn(x, params["router"], params["wi"], wg, params["wo"], shared)


# ---------------------------------------------------------------------------
# decode-2D: weight-stationary expert parallelism for small token counts
# ---------------------------------------------------------------------------


def moe_decode_2d_local(params: dict, xt: jax.Array, cfg: ModelConfig,
                        data_axis: str, model_axis: str) -> jax.Array:
    """Per-shard body: experts sharded over DATA, expert-FFN over MODEL,
    tokens REPLICATED. No weight ever moves; the only collective is the
    psum of the (tiny) combined activations over both axes.

    Right for decode: T = global_batch tokens/step, so activations are ~MBs
    while a 1T MoE's weights are TBs — the per-step all-gather of ZeRO-style
    sharded weights that dominates naive decode disappears entirely.
    """
    t, d = xt.shape
    e_local = params["wi"].shape[0]
    dp = jax.lax.axis_size(data_axis)
    e_lo = jax.lax.axis_index(data_axis) * e_local
    num_experts = e_local * dp
    ids, w, _ = _router_topk(xt, params["router"], cfg.top_k)
    capacity = max(1, int(t * cfg.top_k / num_experts * cfg.capacity_factor)
                   + 1)
    slot_src, slot_w, slot_valid = _dispatch_plan(ids, w, e_lo, e_local,
                                                  capacity)
    x_buf = _dispatch_gather(xt, slot_src, slot_valid, e_local, capacity)
    # expert FFN with the hidden dim sharded over the model axis: gate and
    # up slices align, the down-projection contracts the local F slice ->
    # every shard holds a PARTIAL (over model) of its experts' outputs
    y_buf = _expert_mlp(x_buf, params["wi"], params["wo"], cfg.activation,
                        params.get("wg"))
    y = _combine_scatter(y_buf, slot_src, slot_w, t)
    return jax.lax.psum(y, (data_axis, model_axis))


def moe_decode_2d_forward(params: dict, x: jax.Array, cfg: ModelConfig,
                          mesh) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape

    def body(xt, router, wi, wg, wo):
        p = {"router": router, "wi": wi, "wo": wo}
        if wg is not None:
            p["wg"] = wg
        y = moe_decode_2d_local(p, xt.reshape(-1, d), cfg, "data", "model")
        return y.reshape(b, s, d)

    wg = params.get("wg")
    fn = _shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, None),        # tokens replicated (tiny)
                  P(None, None),               # router replicated
                  P("data", None, "model"),    # wi: E over data, F over model
                  None if wg is None else P("data", None, "model"),
                  P("data", "model", None)),   # wo
        out_specs=P(None, None, None))
    y = fn(x, params["router"], params["wi"], wg, params["wo"])
    if cfg.num_shared_experts:
        # shared expert outside the shard_map (plain TP einsum, XLA handles)
        ys = _shared_expert(params, x.reshape(-1, d), cfg.activation)
        y = y + ys.reshape(b, s, d)
    return y, jnp.zeros((), jnp.float32)


def moe_forward(params: dict, x: jax.Array, cfg: ModelConfig, mesh=None,
                impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    if impl == "auto":
        impl = "dropping" if mesh is not None else "dense"
    if impl == "decode2d":
        if mesh is None:  # single-device fallback: same math, no collectives
            b, s, d = x.shape
            y, aux = moe_dropping_local(params, x.reshape(-1, d), cfg,
                                        None, None)
            return y.reshape(b, s, d), aux
        return moe_decode_2d_forward(params, x, cfg, mesh)
    if impl == "dense":
        return moe_dense_forward(params, x, cfg)
    if impl == "dropping":
        if mesh is None:
            b, s, d = x.shape
            y, aux = moe_dropping_local(params, x.reshape(-1, d), cfg, None, None)
            return y.reshape(b, s, d), aux
        return moe_dropping_forward(params, x, cfg, mesh)
    raise ValueError(impl)
