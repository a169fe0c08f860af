"""Live continuous-batching serving engine (runs real models).

One engine per tier. Fixed-slot design: ``max_batch`` decode slots share a
static-shaped KV cache (per-slot write indices — see models/*); finished
slots are freed and immediately refilled (continuous batching).

The hot path is device-resident:

* **Fused decode** — one jitted ``lax.scan`` runs ``fused_steps`` decode
  steps per host call: on-device greedy/temperature sampling from a per-slot
  ``jax.random`` key array, per-slot alive flags masking EOS / token-budget /
  capacity, and in-place KV writes (the cache and key buffers are DONATED,
  so XLA updates them in place instead of copying the whole cache every
  step). The host receives a single ``(B, fused_steps)`` token block — one
  transfer and one Python bookkeeping pass per K tokens instead of per
  token.
* **Bucketed prefill** — waiting prompts are right-padded to power-of-two
  length buckets and all same-bucket requests prefill in ONE batched call;
  a jitted ``at[...].set`` scatter (donated pool) inserts every admitted
  row into its slot, replacing the per-request retrace and the full-tree
  cache copy. Families whose cache embeds recurrent/ring state (ssm,
  hybrid) or non-neutral pad tokens (moe) batch same-length groups instead
  of padded buckets.

``ServingConfig(fused_steps=1)`` keeps the legacy per-token path (host-side
numpy sampling, one dispatch + ``np.asarray(logits)`` sync per token, one
retraced prefill per request) for parity testing and as the perf baseline.
Token-for-token parity is guaranteed for every family with the default MoE
impl; a capacity-dropping MoE impl is batch-content-coupled by construction
(which tokens drop depends on the other rows in flight), so under it any
two scheduling policies — including legacy vs fused — may diverge.

Fault tolerance: every mutation of engine state is journaled; ``snapshot()``/
``restore()`` allow a failed tier to be rebuilt on a standby (exercised in
tests), and a watchdog marks the engine unhealthy if a step exceeds the
heartbeat timeout.

Cross-tier KV migration: ``extract_slot(rid)`` serializes ONE request's
cache rows (the same axis-aware leaf walk the prefill scatter uses), its
``SeqState`` and its sampling key into a :class:`SlotPayload` with a
versioned, dtype/shape-tagged wire format; ``inject_slot(payload)`` resumes
it in a free slot of a compatible engine without re-prefilling.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import struct
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig, ServingConfig
from repro.serving.paged import PagePool, pages_needed
from repro.serving.prefix import (ParkedSession, PrefixStore, SessionStore,
                                  extension_suffix, extras_fingerprint,
                                  prefix_buckets)

# Families whose decode cache is a full-capacity absolute-position buffer:
# right-padded bucket prefill is exact for them (pad entries are masked via
# the ``pos`` leaf; causal attention keeps real-token activations identical).
# ssm/hybrid carry recurrent/ring state that pads would shift — they batch
# exact same-length groups instead. moe prefills per request: a capacity-
# dropping MoE impl routes over the flattened batch, so ANY cross-request
# batching (padded or not) would couple which tokens drop across requests.
_PADDED_FAMILIES = ("dense", "vlm", "encdec")


def _next_bucket(n: int, lo: int = 16) -> int:
    """Smallest power-of-two >= n (floored at ``lo`` to bound trace count)."""
    b = lo
    while b < n:
        b *= 2
    return b


def block_pages(positions: np.ndarray, budgets: np.ndarray, k: int,
                teff: int, page: int, max_seq: int) -> tuple:
    """``(pages_grid, pages_live)`` of one fused block's paged decode: the
    page steps of the kernel's grid over its ``k`` steps, and those it
    computes, up to each row's last live page. A row alive at the block's
    start (``budgets > 0``) writes position ``positions + min(j, budgets)``
    at step ``j`` (it stops at its budget or the cache's end) and computes
    ``pos // page + 1`` pages. Any other row counts one, its dead write's
    entry at page 0; a slot freed earlier whose stale write index lies in
    the bucket computes up to that index's page, which is left out."""
    n_pg = -(-teff // page)
    pos = positions[:, None] + np.minimum(np.arange(k)[None],
                                          budgets[:, None])
    live = np.where(budgets[:, None] > 0,
                    np.minimum(np.minimum(pos, max_seq - 1) // page + 1,
                               n_pg), 1)
    return k * len(positions) * n_pg, int(live.sum())


@dataclass
class SeqState:
    rid: int
    prompt_len: int
    generated: List[int] = field(default_factory=list)
    max_new: int = 32
    done: bool = False
    t_submit: float = 0.0
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None
    # multi-turn session id: when set, the slot's state is parked into the
    # engine's session store at finish so the next turn resumes it
    session: Optional[str] = None


# ---------------------------------------------------------------------------
# cross-tier KV migration: per-slot cache wire format
# ---------------------------------------------------------------------------

#: bump when the on-wire layout changes; injectors reject other versions
#: v2: CRC32 on the header blob and on every raw buffer (key + each leaf)
#: so byte corruption anywhere on the wire deterministically raises
#: MigrationError instead of silently injecting garbage KV
MIGRATION_WIRE_VERSION = 2
_WIRE_MAGIC = b"MOAKV"
_WIRE_HDR = struct.Struct("<HII")  # (version, header len, header crc32)


class MigrationError(RuntimeError):
    """A slot payload cannot be extracted or injected: unknown wire version,
    wrong model/family, mismatched cache geometry, or no free slot. Raised
    BEFORE any engine state is mutated, so a failed injection leaves the
    target engine untouched (callers fall back to a fresh prefill)."""


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype tag, including the ml_dtypes extras jax uses. An
    unknown tag (corrupt header, sender with newer dtypes) raises
    MigrationError so callers keep their re-prefill fallback."""
    try:
        return np.dtype(name)
    except TypeError:
        try:
            import ml_dtypes  # ships with jax

            return np.dtype(getattr(ml_dtypes, name))
        except (AttributeError, TypeError) as e:
            raise MigrationError(
                f"unknown dtype tag {name!r} in slot payload") from e


@dataclass
class SlotPayload:
    """One request's migratable state: its per-slot cache rows (every leaf
    sliced along that leaf's logical batch axis — dense/vlm/moe KV, ssm
    conv+state, hybrid ring/rglru leaves), its ``SeqState``, the absolute
    next position, and the per-slot sampling key. ``to_bytes``/``from_bytes``
    are the versioned, dtype/shape-tagged wire format the live backend
    actually ships across tiers."""

    version: int
    model: str
    family: str
    max_seq: int
    seq: SeqState
    position: int
    key: np.ndarray  # per-slot jax.random key data
    leaves: Dict[str, np.ndarray]  # keystr(cache path) -> per-slot row
    # prompt token ids (session park/resume needs to know exactly which
    # tokens the cache rows cover); absent on wires from older senders
    prompt_tokens: Optional[np.ndarray] = None
    # fingerprint of the prefill extras (vision patches) occupying cache
    # positions — a resume must present identical extras to reuse the rows
    extras_fp: bytes = b""
    _wire: Optional[bytes] = field(default=None, repr=False, compare=False)

    @property
    def nbytes(self) -> int:
        """Exact on-wire size (serialized lazily, cached)."""
        return len(self.to_bytes())

    def to_bytes(self) -> bytes:
        if self._wire is not None:
            return self._wire
        seq = self.seq
        names = sorted(self.leaves)
        key_bytes = np.ascontiguousarray(self.key).tobytes()
        leaf_bytes = [np.ascontiguousarray(self.leaves[n]).tobytes()
                      for n in names]
        head = {
            "version": self.version,
            "model": self.model,
            "family": self.family,
            "max_seq": self.max_seq,
            "position": self.position,
            "seq": {
                "rid": seq.rid, "prompt_len": seq.prompt_len,
                "generated": list(seq.generated), "max_new": seq.max_new,
                "done": seq.done, "t_submit": seq.t_submit,
                "t_first_token": seq.t_first_token, "t_done": seq.t_done,
                "session": seq.session,
            },
            "prompt_tokens": (None if self.prompt_tokens is None
                              else [int(t) for t in self.prompt_tokens]),
            "extras_fp": self.extras_fp.hex(),
            "key": {"dtype": str(self.key.dtype),
                    "shape": list(self.key.shape),
                    "crc": zlib.crc32(key_bytes)},
            "leaves": [{"name": n, "dtype": str(self.leaves[n].dtype),
                        "shape": list(self.leaves[n].shape),
                        "crc": zlib.crc32(raw)}
                       for n, raw in zip(names, leaf_bytes)],
        }
        blob = json.dumps(head).encode("utf-8")
        parts = [_WIRE_MAGIC,
                 _WIRE_HDR.pack(self.version, len(blob), zlib.crc32(blob)),
                 blob, key_bytes]
        parts += leaf_bytes
        self._wire = b"".join(parts)
        return self._wire

    @classmethod
    def from_bytes(cls, wire: bytes) -> "SlotPayload":
        m = len(_WIRE_MAGIC)
        if wire[:m] != _WIRE_MAGIC:
            raise MigrationError("not a slot payload (bad magic)")
        if len(wire) < m + _WIRE_HDR.size:
            raise MigrationError("truncated slot payload")
        version, hlen, hcrc = _WIRE_HDR.unpack_from(wire, m)
        if version != MIGRATION_WIRE_VERSION:
            raise MigrationError(
                f"wire format version {version} != supported "
                f"{MIGRATION_WIRE_VERSION}")
        off = m + _WIRE_HDR.size
        if off + hlen > len(wire):
            raise MigrationError("truncated slot payload header")
        blob = wire[off:off + hlen]
        # the header checksum gates json parsing: corrupt bytes raise here,
        # deterministically, before anything is interpreted
        if zlib.crc32(blob) != hcrc:
            raise MigrationError("slot payload header checksum mismatch")

        def pull(dtype_s: str, shape, crc) -> np.ndarray:
            nonlocal off
            dt = _np_dtype(dtype_s)
            if any(int(d) < 0 for d in shape):
                raise MigrationError(f"corrupt leaf shape {shape}")
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            end = off + n * dt.itemsize
            if end > len(wire):
                raise MigrationError("truncated slot payload")
            raw = wire[off:end]
            if crc is not None and zlib.crc32(raw) != crc:
                raise MigrationError("slot payload buffer checksum mismatch")
            arr = np.frombuffer(raw, dtype=dt).reshape(shape).copy()
            off = end
            return arr

        # any malformation beyond this point (bad json, missing header
        # fields, bogus shapes) is a corrupt wire, never a crash: callers
        # rely on MigrationError to fall back to a fresh prefill
        try:
            head = json.loads(blob.decode("utf-8"))
            off += hlen
            key = pull(head["key"]["dtype"], head["key"]["shape"],
                       head["key"].get("crc"))
            leaves = {d["name"]: pull(d["dtype"], d["shape"], d.get("crc"))
                      for d in head["leaves"]}
            s = head["seq"]
            seq = SeqState(rid=s["rid"], prompt_len=s["prompt_len"],
                           generated=list(s["generated"]),
                           max_new=s["max_new"], done=s["done"],
                           t_submit=s["t_submit"],
                           t_first_token=s["t_first_token"],
                           t_done=s["t_done"],
                           session=s.get("session"))
            pt = head.get("prompt_tokens")
            return cls(version=version, model=head["model"],
                       family=head["family"], max_seq=head["max_seq"],
                       seq=seq, position=head["position"], key=key,
                       leaves=leaves,
                       prompt_tokens=(None if pt is None
                                      else np.asarray(pt, np.int32)),
                       extras_fp=bytes.fromhex(head.get("extras_fp", "")),
                       _wire=bytes(wire))
        except MigrationError:
            raise
        except (KeyError, ValueError, TypeError, OverflowError) as e:
            raise MigrationError(f"corrupt slot payload: {e!r}") from e


class TierEngine:
    def __init__(self, model, params, serving: ServingConfig = ServingConfig(),
                 eos_id: int = 2, sample_temp: float = 0.0, seed: int = 0,
                 device=None, tier: str = ""):
        """``device`` pins the engine — params, KV cache, sampling keys —
        to one ``jax.Device`` (replicas of a pool, one per chip); every
        jitted step then runs there. None keeps JAX's default device.
        ``tier`` names the tier served (a span field)."""
        self.device = device
        self.tier = tier
        with jax.default_device(device):
            self._build(model, params, serving, eos_id, sample_temp, seed)
        if device is not None:
            self.params, self.cache, self._keys = jax.device_put(
                (self.params, self.cache, self._keys), device)

    def _build(self, model, params, serving, eos_id, sample_temp, seed):
        # shallow copy: the engine sets per-engine decode flags below and
        # must not leak them into a model object shared with other engines
        model = copy.copy(model)
        self.model = model
        self.cfg: ModelConfig = model.cfg
        self.params = params
        self.serving = serving
        self.eos_id = eos_id
        self.temp = sample_temp
        self.rng = np.random.default_rng(seed)
        self.fused_steps = max(1, serving.fused_steps)
        if serving.decode_impl != "auto" and hasattr(model, "decode_impl"):
            model.decode_impl = serving.decode_impl
        if (self.fused_steps > 1 and serving.unroll_decode_layers
                and hasattr(model, "decode_unroll")):
            model.decode_unroll = True

        b, t = serving.max_batch, serving.max_seq
        self.cache = model.init_cache(b, t)
        self.slots: List[Optional[SeqState]] = [None] * b
        self.positions = np.zeros((b,), np.int64)  # absolute next position
        self.waiting: List[Dict[str, Any]] = []
        self.finished: List[SeqState] = []
        self.journal: List[tuple] = []  # (op, payload) event journal
        self.healthy = True
        # chaos knob: a slow-node fault window sets this > 1 and each step
        # sleeps (throttle-1)x its own duration — the live analogue of the
        # analytic backend's stretched service times. The cap bounds the
        # stretch of outlier steps (compiles, host deschedules) so a slow
        # NODE never emulates a dead one
        self.throttle = 1.0
        self.throttle_sleep_cap_s = 0.5
        self.last_heartbeat = time.monotonic()
        self.steps = 0
        # perf counters (read by benchmarks/serving_bench.py and launch/serve)
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.encode_tokens = 0  # encode-only entry point (partial offload)
        # prefix & session KV reuse counters: tokens whose prefill was
        # SKIPPED because their cache rows were copied from the prefix
        # store / a parked session (prefill_tokens counts only suffixes)
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.resumed_sessions = 0
        self.resumed_tokens = 0
        self.parks = 0
        # cross-tier speculative decoding: tokens this engine PROPOSED as a
        # draft, draft tokens this engine ACCEPTED while verifying as a
        # target, and verify rounds run (accepted/drafted is the live
        # acceptance rate the scheduler's EWMA tracks)
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.spec_rounds = 0
        # cluster-runtime hooks: admission + per-token streaming callbacks
        # (rid, t_admit, t) and (rid, token, t): t_admit is when the job
        # left ``waiting``, t its first token; None = standalone engine
        self.on_admit: Optional[Callable[[int, float, float], None]] = None
        self.on_token: Optional[Callable[[int, int, float], None]] = None
        # warm-admission + session-park hooks: (rid, kind, cached, suffix)
        # with kind in {"prefix", "resume"}, and (rid, sid)
        self.on_warm: Optional[Callable[[int, str, int, int], None]] = None
        self.on_park: Optional[Callable[[int, str], None]] = None
        self._encode_jits: Dict[tuple, Any] = {}

        self._decode = jax.jit(model.decode_step)
        self._prefill1 = jax.jit(lambda p, batch: model.prefill(p, batch, t))
        # per-slot sampling keys live on device across the engine's lifetime
        self._keys = jax.random.split(jax.random.PRNGKey(seed), b)
        # batch axis per cache leaf (hybrid rglru leaves carry batch at
        # axis 2 — the generic scatter below follows the logical axes)
        axes = model.cache_axes(b, t)
        self._cache_batch_axis = jax.tree.map(
            lambda a: a.index("batch"), axes,
            is_leaf=lambda x: isinstance(x, tuple))
        # seq (time) axis per cache leaf, -1 for per-slot state without a
        # time dimension (recurrent h/conv, pos bookkeeping): the prefix
        # store slices KV rows along this axis, the same cache_axes walk
        # extract_slot uses for the batch axis
        self._cache_seq_axis = jax.tree.map(
            lambda a: a.index("seq") if "seq" in a else -1, axes,
            is_leaf=lambda x: isinstance(x, tuple))
        self._axis_by_name = {
            jax.tree_util.keystr(pb[0]): (pb[1], ps[1])
            for pb, ps in zip(
                jax.tree_util.tree_leaves_with_path(self._cache_batch_axis),
                jax.tree_util.tree_leaves_with_path(self._cache_seq_axis))}
        # prefix & session KV reuse: prefixes of admitted prompts are
        # positionally addressable (sliceable at any boundary) only for the
        # full-capacity absolute-position families; ssm/hybrid state is a
        # point-in-time snapshot, so only whole processed sequences park
        self._sliceable = self.cfg.family in ("dense", "vlm", "moe")
        self.prefix_store = PrefixStore(serving.prefix_cache_mb * 1e6,
                                        min_prefix=serving.prefix_min_tokens)
        self.sessions = SessionStore(serving.session_cache_mb * 1e6)
        self._slot_prompt: List[Optional[np.ndarray]] = [None] * b
        self._slot_extras_fp: List[bytes] = [b""] * b

        # -- paged KV pool: block allocator + copy-free CoW prefix sharing --
        # sliceable families move their big KV leaves into a shared physical
        # pool ((L, P, page, K, hd) + an int32 page table per slot); the
        # recurrent families keep exact-length dense state but share the pool
        # ACCOUNTING so cross-family tiers report comparable headroom.
        self.pool: Optional[PagePool] = None
        self._paged_names: tuple = ()
        self._dense_spec_tree = None  # dense cache template (paged engines)
        self._pt: Optional[np.ndarray] = None  # host page tables (B, n_pt)
        self._slot_pages: List[List[int]] = [[] for _ in range(b)]
        self._page_pressure = False  # set when admission starved for pages
        self._slot_page_charge = 1  # accounting charge (recurrent families)
        self._n_pt = 0
        self._slots_hw = 0  # dense-mode occupied-slot high-water (gauges)
        if serving.paged:
            ps = serving.kv_page_size
            self._n_pt = serving.pages_per_slot
            self._dense_spec_tree = jax.eval_shape(
                lambda: model.init_cache(b, t))
            page_bytes = 0.0
            if self._sliceable:
                paged_names = []
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                        self._dense_spec_tree):
                    name = jax.tree_util.keystr(path)
                    if name in ("['pos']", "['index']"):
                        continue  # per-slot bookkeeping stays dense
                    bax, sax = self._axis_by_name[name]
                    if sax == bax + 1 and leaf.shape[sax] == t:
                        paged_names.append(name)
                        rowb = (float(np.prod(leaf.shape, dtype=np.float64))
                                / leaf.shape[bax] / leaf.shape[sax]
                                * jnp.dtype(leaf.dtype).itemsize)
                        page_bytes += rowb * ps
                self._paged_names = tuple(sorted(paged_names))
                npages = serving.pool_pages + 1  # + pinned null page 0

                def repage(path, leaf):
                    name = jax.tree_util.keystr(path)
                    if name not in self._paged_names:
                        return leaf
                    bax = self._axis_by_name[name][0]
                    shape = (leaf.shape[:bax] + (npages, ps)
                             + leaf.shape[bax + 2:])
                    return jnp.zeros(shape, leaf.dtype)

                self.cache = jax.tree_util.tree_map_with_path(
                    repage, self.cache)
                self.cache["pages"] = jnp.zeros((b, self._n_pt), jnp.int32)
                self._pt = np.zeros((b, self._n_pt), np.int32)
            else:
                # per-slot state is point-in-time (no positional pages);
                # charge each slot the pages its largest time-axis leaf
                # would occupy so admission answers to the same pool
                max_rows = 0
                slot_bytes = 0.0
                for name, leaf, bax in self._leaf_rows():
                    slot_bytes += leaf.nbytes / leaf.shape[bax]
                    sax = self._axis_by_name[name][1]
                    if sax >= 0 and name not in ("['pos']", "['index']"):
                        max_rows = max(max_rows, leaf.shape[sax])
                self._slot_page_charge = max(
                    1, pages_needed(max_rows, ps, t))
                page_bytes = slot_bytes / self._slot_page_charge
            self.pool = PagePool(serving.pool_pages, ps,
                                 page_bytes=page_bytes)
        # dense per-slot row geometry (name -> (shape, dtype)) for payload
        # validation — structure-independent, so paged and dense engines
        # speak the same migration wire format
        spec_src = (self._dense_spec_tree if self._dense_spec_tree is not None
                    else self.cache)
        self._row_specs: Dict[str, tuple] = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(spec_src):
            name = jax.tree_util.keystr(path)
            bax = self._axis_by_name[name][0]
            self._row_specs[name] = (
                leaf.shape[:bax] + leaf.shape[bax + 1:], str(leaf.dtype))
        if self.pool is not None:
            # dropping a store reference and freeing its physical pages must
            # never diverge: EVERY store removal (LRU eviction, overwrite,
            # explicit pop) decrefs through this hook
            self.prefix_store.lru.on_evict = self._on_prefix_evict
        self._warm_chunk_recurrent = None
        if (serving.chunked_recurrent_suffix
                and self.cfg.family in ("ssm", "hybrid")
                and hasattr(model, "decode_chunk_recurrent")):
            self._warm_chunk_recurrent = jax.jit(
                model.decode_chunk_recurrent, donate_argnums=(1,))

        self._warm_scan = jax.jit(self._make_warm_scan(),
                                  donate_argnums=(1,), static_argnums=(4,))
        max_seq = self.serving.max_seq
        self._warm_chunk = jax.jit(
            lambda p, c, batch, teff: model.decode_chunk(
                p, c, batch, ctx=(teff if teff < max_seq else None)),
            donate_argnums=(1,), static_argnums=(3,))
        # context buckets need linear cache placement (pos == write index),
        # which holds for the full-capacity-"pos" families only — ring
        # windows (hybrid), recurrent state (ssm) and the encdec cross
        # cache keep full-width attention
        self._ctx_buckets = (serving.context_buckets
                             and self.cfg.family in ("dense", "vlm", "moe"))
        self._fused = jax.jit(self._make_fused(), donate_argnums=(1, 2),
                              static_argnums=(6, 7))
        self._prefill_insert = jax.jit(
            self._make_prefill_insert(), donate_argnums=(1,),
            static_argnums=(6,) if self._pt is not None else ())
        # speculative-decoding jits, built lazily on first use: the verify
        # chunk (decode_chunk/-_recurrent with all_logits) and the batch-1
        # autoregressive draft scan
        self._spec_chunk_fn = None
        self._spec_draft_fn = None

    # ------------------------------------------------------------------
    # jitted hot-path builders
    # ------------------------------------------------------------------

    def _make_fused(self):
        """K decode steps in one lax.scan: decode -> on-device sample ->
        per-slot EOS/budget/cap masking. Cache + keys are donated by the
        caller so the KV buffers update in place.

        ``teff`` (static) is the context bucket: attention reads only the
        leading ``teff`` cache entries (a static slice XLA fuses into the
        attention loads — writes and bookkeeping stay full-width, so the
        cache layout never changes). Valid because placement is linear for
        the bucket-eligible families, every admit rewrites a slot's full
        row (entries past a prompt carry pos=-1), and the engine picks
        ``teff`` above the longest position reached inside the block.
        """
        model = self.model
        temp, eos = float(self.temp), int(self.eos_id)
        max_seq = int(self.serving.max_seq)
        # ssm/hybrid carry recurrent state (and a ring window whose write
        # index wraps onto LIVE entries): a dead slot's decode_step would
        # keep mutating it, corrupting the very rows session parking
        # extracts at finish. Freeze the whole dead row for those families;
        # the full-capacity-pos families only need the pos/index freeze
        # (dead writes land on pos=-1 entries, which reads mask out).
        freeze_rows = self.cfg.family in ("ssm", "hybrid")
        bax_tree = self._cache_batch_axis

        def fused(params, cache, keys, tokens, positions, budgets, teff,
                  k_steps):
            # ``k_steps`` (static) shrinks the block below ``fused_steps``
            # under page pressure: the host re-checks admission the moment a
            # finishing request can free pages (continuous admission splits
            # the scan at the pressure boundary instead of waiting K steps)
            ctx = teff if teff < max_seq else None

            def body(carry, _):
                cache, keys, tok, pos, alive, produced = carry
                logits, cache2 = model.decode_step(
                    params, cache, {"tokens": tok[:, None], "positions": pos},
                    ctx=ctx)
                if temp > 0:
                    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                    keys2, sub = split[:, 0], split[:, 1]
                    sampled = jax.vmap(jax.random.categorical)(
                        sub, logits / temp)
                else:
                    keys2 = keys
                    sampled = jnp.argmax(logits, axis=-1)
                sampled = sampled.astype(jnp.int32)
                produced = produced + alive.astype(jnp.int32)
                pos2 = jnp.where(alive, pos + 1, pos)
                # same stop rules the host applies per appended token
                alive2 = (alive & (sampled != eos) & (produced < budgets)
                          & (pos2 + 1 < max_seq))
                tok2 = jnp.where(alive, sampled, tok)
                if freeze_rows:
                    # keep a dead slot's ENTIRE cache row at its
                    # time-of-death state (recurrent h/conv + ring KV +
                    # bookkeeping); rows are small for these families
                    def keep(new, old, bax):
                        shape = [1] * new.ndim
                        shape[bax] = alive.shape[0]
                        return jnp.where(alive.reshape(shape), new, old)

                    cache2 = jax.tree.map(keep, cache2, cache, bax_tree)
                elif "pos" in cache2:
                    # freeze write bookkeeping of dead slots: their KV writes
                    # land on a slot whose pos stays -1 (masked), instead of
                    # marching the ring index over live-looking entries
                    cache2 = dict(cache2)
                    cache2["pos"] = jnp.where(alive[:, None], cache2["pos"],
                                              cache["pos"])
                    cache2["index"] = jnp.where(alive, cache2["index"],
                                                cache["index"])
                return (cache2, keys2, tok2, pos2, alive2, produced), tok2

            alive0 = budgets > 0
            produced0 = jnp.zeros_like(budgets)
            (cache, keys, *_), toks = jax.lax.scan(
                body, (cache, keys, tokens, positions, alive0, produced0),
                None, length=k_steps)
            return jnp.transpose(toks), cache, keys  # (B, k_steps)

        return fused

    def _make_prefill_insert(self):
        """Batched prefill + donated multi-row scatter into the slot pool.

        ``slots`` (R,) are the destination slot ids (duplicates allowed only
        for padded rows carrying identical values); ``total`` (R,) is each
        row's true sequence length INCLUDING any vision prefix.

        Paged engines take an extra ``pt_rows`` (R, n_pt) page-table
        argument: the big KV leaves scatter THROUGH the tables into the
        physical pool (a row's unreserved tail maps to the null page — its
        writes are garbage-by-construction and masked via ``pos``), while
        pos/index keep the per-slot scatter.
        """
        model = self.model
        capacity = self.serving.max_seq
        pad_ok = self.cfg.family in _PADDED_FAMILIES

        def remask(cache1, total):
            if pad_ok and "pos" in cache1:
                cache1 = dict(cache1)
                cap = cache1["pos"].shape[1]
                # pad entries carry absolute positions >= the row's true
                # length -> mark empty; next write lands at the true end
                cache1["pos"] = jnp.where(cache1["pos"] < total[:, None],
                                          cache1["pos"], -1)
                cache1["index"] = (jnp.minimum(total, cap) % cap).astype(
                    jnp.int32)
            return cache1

        if self._pt is None:
            def fn(params, pool, batch, slots, total):
                logits, cache1 = model.prefill(params, batch, capacity)
                cache1 = remask(cache1, total)

                def ins(pool_leaf, one, bax):
                    idx = (slice(None),) * bax + (slots,)
                    return pool_leaf.at[idx].set(one.astype(pool_leaf.dtype))

                pool = jax.tree.map(ins, pool, cache1,
                                    self._cache_batch_axis)
                return logits, pool

            return fn

        n_pt, page = self._n_pt, self.serving.kv_page_size
        paged_names = self._paged_names
        axis_by_name = self._axis_by_name

        def fn_paged(params, pool, batch, slots, total, pt_rows, npg):
            # ``npg`` (static) is the group's reserved-page high-water: only
            # the leading npg pages per row scatter into the pool (the tail
            # past a prompt's reservation is all null-page writes — skipping
            # it keeps the scatter near the dense path's cost)
            logits, cache1 = model.prefill(params, batch, capacity)
            cache1 = remask(cache1, total)
            pool = dict(pool)
            pages_leaf = pool.pop("pages")
            pt_sl = pt_rows[:, :npg]

            def ins(path, pool_leaf, one):
                name = jax.tree_util.keystr(path)
                bax = axis_by_name[name][0]
                if name in paged_names:
                    one = one.reshape(one.shape[:bax + 1] + (n_pt, page)
                                      + one.shape[bax + 2:])
                    one = one[(slice(None),) * (bax + 1)
                              + (slice(0, npg),)]
                    idx = (slice(None),) * bax + (pt_sl,)
                else:
                    idx = (slice(None),) * bax + (slots,)
                return pool_leaf.at[idx].set(one.astype(pool_leaf.dtype))

            pool = jax.tree_util.tree_map_with_path(ins, pool, cache1)
            pool["pages"] = pages_leaf.at[slots].set(pt_rows)
            return logits, pool

        return fn_paged

    def _make_warm_scan(self):
        """Suffix prefill for prefix-cache hits / resumed sessions: run the
        model's own ``decode_step`` over the suffix tokens in ONE jitted
        ``lax.scan`` against a batch-1 cache holding the reused rows.

        This is exact by construction — it is the very path the engine
        decodes with, so KV/state updates and logits match what a cold full
        prefill followed by decode would produce (bit-identical for the
        full-capacity KV families, within fp noise for ssm/hybrid
        recurrences) — and it costs one host dispatch per suffix instead of
        one per token. One trace is compiled per (suffix length, context
        bucket); sliceable families right-pad the suffix to the
        power-of-two ladder (pad writes land past the true end and are
        masked via ``pos``, exactly like bucketed prefill pads). ``teff``
        is the same context-bucket hint fused decode uses: attention reads
        only the leading ``teff`` cache entries instead of all of
        ``max_seq``.
        """
        model = self.model
        max_seq = int(self.serving.max_seq)

        def fn(params, cache, tokens, positions, teff):  # (T,), (T,)
            ctx = teff if teff < max_seq else None

            def body(c, tp):
                tok, pos = tp
                logits, c2 = model.decode_step(
                    params, c, {"tokens": tok[None, None],
                                "positions": pos[None]}, ctx=ctx)
                return c2, logits[0]

            cache, logits = jax.lax.scan(body, cache, (tokens, positions))
            return logits, cache

        return fn

    def _context_bucket(self, needed: int) -> int:
        """Smallest {2^n, 1.5*2^n} ladder value covering ``needed``
        positions (each bucket is one cached trace)."""
        teff = 32
        while teff < needed:
            teff = teff * 3 // 2 if teff & (teff - 1) == 0 else teff * 4 // 3
        return min(teff, self.serving.max_seq)

    # ------------------------------------------------------------------

    def submit(self, rid: int, tokens: np.ndarray, max_new: int = 32,
               extras: Optional[Dict[str, np.ndarray]] = None,
               deadline: Optional[float] = None,
               session: Optional[str] = None) -> None:
        """Queue a prompt. ``deadline`` (monotonic seconds) enables
        EDF-ordered admission: the waiting queue is drained
        earliest-deadline-first instead of FIFO. ``session`` names a
        multi-turn session: the finished turn's slot state is parked so a
        later turn whose prompt extends this conversation resumes it
        (prefilling only the new tokens) instead of re-prefilling the whole
        history."""
        self.journal.append(("submit", {"rid": rid, "tokens": tokens,
                                        "max_new": max_new,
                                        "extras": extras,
                                        "deadline": deadline,
                                        "session": session}))
        self.waiting.append({"rid": rid, "tokens": np.asarray(tokens),
                             "max_new": max_new, "extras": extras or {},
                             "deadline": deadline, "session": session,
                             "t": time.monotonic()})

    def cancel(self, rid: int) -> bool:
        """Abort a request wherever it is (waiting or mid-decode). The
        cluster runtime uses this to retire the losing hedge twin; the freed
        slot is refilled at the next admission."""
        for i, j in enumerate(self.waiting):
            if j["rid"] == rid:
                del self.waiting[i]
                self.journal.append(("cancel", {"rid": rid}))
                return True
        for i, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                self.slots[i] = None  # KV rows are overwritten on next admit
                self._release_pages(i)
                self.journal.append(("cancel", {"rid": rid}))
                return True
        return False

    # -- cross-tier KV migration -------------------------------------------

    def _leaf_rows(self):
        """Yield ``(name, leaf, batch_axis)`` per cache leaf — the same
        axis-aware walk the prefill scatter uses (``cache_axes``-driven, so
        hybrid rglru leaves with batch at axis 2 come out right)."""
        flat = jax.tree_util.tree_leaves_with_path(self.cache)
        axes = jax.tree.leaves(self._cache_batch_axis)
        for (path, leaf), bax in zip(flat, axes):
            yield jax.tree_util.keystr(path), leaf, bax

    def _slot_payload(self, slot: int) -> SlotPayload:
        """Serialize slot ``slot``'s full migratable state (cache rows,
        SeqState, position, sampling key, prompt tokens). The rows stay
        DEVICE-resident (``jnp.take`` copies out of the donated pool); the
        wire format converts to host bytes lazily, so a payload parked and
        resumed on the same tier never round-trips through the host."""
        if self._pt is not None:
            leaves = self._gather_slot_rows(slot)
        else:
            leaves = {name: jnp.take(leaf, slot, axis=bax)
                      for name, leaf, bax in self._leaf_rows()}
        return SlotPayload(
            version=MIGRATION_WIRE_VERSION, model=self.cfg.name,
            family=self.cfg.family, max_seq=self.serving.max_seq,
            seq=self._copy_seq(self.slots[slot]),
            position=int(self.positions[slot]),
            key=np.asarray(self._keys[slot]), leaves=leaves,
            prompt_tokens=self._slot_prompt[slot],
            extras_fp=self._slot_extras_fp[slot])

    def extract_slot(self, rid: int, *, remove: bool = False) -> SlotPayload:
        """Serialize one request's migratable state (see ``SlotPayload``).
        ``remove=True`` frees the slot (preemption / re-homing); the default
        keeps the donor decoding (hedged clone races the original)."""
        slot = next((i for i, s in enumerate(self.slots)
                     if s is not None and s.rid == rid), None)
        if slot is None:
            raise MigrationError(
                f"rid {rid} holds no decode slot on this engine")
        payload = self._slot_payload(slot)
        if remove:
            self.slots[slot] = None  # KV rows overwritten on the next admit
            self._release_pages(slot)
        self.journal.append(("extract", {"rid": rid, "removed": remove}))
        return payload

    def inject_slot(self, payload: SlotPayload) -> int:
        """Install a migrated request into a free slot and resume its decode
        exactly where the donor left off (no prefill — ``prefill_tokens``
        does not move). Validates the wire version, model spec and every
        leaf's shape/dtype BEFORE touching the cache; any mismatch raises
        :class:`MigrationError` and leaves this engine unchanged."""
        if payload.version != MIGRATION_WIRE_VERSION:
            raise MigrationError(
                f"wire format version {payload.version} != supported "
                f"{MIGRATION_WIRE_VERSION}")
        if payload.model != self.cfg.name or payload.family != self.cfg.family:
            raise MigrationError(
                f"payload from {payload.model!r} ({payload.family}) cannot be "
                f"injected into {self.cfg.name!r} ({self.cfg.family}): KV "
                f"caches are model-specific")
        if any(s is not None and s.rid == payload.seq.rid for s in self.slots):
            raise MigrationError(
                f"rid {payload.seq.rid} already occupies a slot here")
        slot = self._free_slot()
        if slot is None:
            raise MigrationError("no free decode slot to inject into")
        rows = dict(payload.leaves)
        if set(self._row_specs) != set(rows):
            raise MigrationError(
                f"cache leaf mismatch: payload has {sorted(rows)}, engine "
                f"expects {sorted(self._row_specs)}")
        for name, (want, dtype) in self._row_specs.items():
            row = rows[name]
            if tuple(row.shape) != tuple(want):
                raise MigrationError(
                    f"leaf {name}: payload row shape {tuple(row.shape)} != "
                    f"engine row shape {tuple(want)} (max_seq "
                    f"{payload.max_seq} vs {self.serving.max_seq}?)")
            if str(row.dtype) != dtype:
                raise MigrationError(
                    f"leaf {name}: payload dtype {row.dtype} != engine "
                    f"dtype {dtype}")
        if self.pool is not None:
            seq = payload.seq
            total = min(payload.position
                        + max(int(seq.max_new) - len(seq.generated), 0) + 1,
                        self.serving.max_seq)
            pages = self._reserve_pages(self._page_need(total))
            if pages is None:
                raise MigrationError(
                    "no free KV pages to inject into (pool exhausted)")
            self._assign_pages(slot, pages)
        self._install_rows(slot, rows)
        self.slots[slot] = self._copy_seq(payload.seq)
        self.positions[slot] = payload.position
        self._keys = self._keys.at[slot].set(self._put(payload.key))
        self._slot_prompt[slot] = (None if payload.prompt_tokens is None
                                   else np.asarray(payload.prompt_tokens))
        self._slot_extras_fp[slot] = payload.extras_fp
        self.journal.append(("inject", {"rid": payload.seq.rid, "slot": slot}))
        return slot

    # -- prefix & session KV reuse -----------------------------------------

    def _job_fp(self, job: Dict[str, Any]) -> bytes:
        """Extras fingerprint of a waiting job, computed once and cached."""
        fp = job.get("_fp")
        if fp is None:
            fp = extras_fingerprint(job["extras"])
            job["_fp"] = fp
        return fp

    def _rows_compatible(self, rows: Dict[str, np.ndarray]) -> bool:
        """True when ``rows`` (keystr -> per-slot row) matches this engine's
        DENSE per-slot row geometry exactly (same leaves, shapes, dtypes).
        Paged and dense engines share the geometry — the wire format is
        structure-independent, so payloads migrate across pool designs."""
        if set(rows) != set(self._row_specs):
            return False
        for name, (shape, dtype) in self._row_specs.items():
            row = rows[name]
            if (tuple(row.shape) != tuple(shape)
                    or str(row.dtype) != dtype):
                return False
        return True

    def _payload_resumable(self, p: SlotPayload) -> bool:
        return (p.version == MIGRATION_WIRE_VERSION
                and p.model == self.cfg.name
                and p.family == self.cfg.family
                and p.max_seq == self.serving.max_seq
                and self._rows_compatible(p.leaves))

    @staticmethod
    def _payload_tokens(p: SlotPayload) -> Optional[np.ndarray]:
        """The tokens a payload's cache rows cover: the prompt plus every
        generated token except the last (sampled but never fed)."""
        if p.prompt_tokens is None:
            return None
        prompt = np.asarray(p.prompt_tokens, np.int32)
        gen = np.asarray(p.seq.generated[:-1], np.int32)
        return np.concatenate([prompt, gen]) if gen.size else prompt

    def rids(self) -> List[int]:
        """Every request currently on this engine (queued + in a slot), in
        queue-then-slot order — the replica-pool fault path replays against
        this set."""
        out = [j["rid"] for j in self.waiting]
        out.extend(s.rid for s in self.slots if s is not None)
        return out

    def free_slot_count(self) -> int:
        """Open slots (the pool's load-balance / re-home capacity probe)."""
        return sum(s is None for s in self.slots)

    def park_session(self, rid: int, sid: Optional[str] = None) -> bool:
        """Mark a queued or in-flight request so its slot state parks under
        ``sid`` when it finishes (``submit(session=...)`` does this up
        front). Returns False when the rid is unknown or no sid is set."""
        for j in self.waiting:
            if j["rid"] == rid:
                j["session"] = sid or j.get("session")
                return j["session"] is not None
        for s in self.slots:
            if s is not None and s.rid == rid:
                s.session = sid or s.session
                return s.session is not None
        return False

    def resume_session(self, sid: str) -> Optional[ParkedSession]:
        """Pop a parked session (the caller consumes its rows). Admission
        does this internally; it is public for cross-tier moves."""
        return self.sessions.resume(sid)

    def adopt_session(self, sid: str, payload: SlotPayload) -> bool:
        """Install a session payload parked on ANOTHER engine (the sticky
        router moved it here). Incompatible payloads are refused — the next
        turn then falls back to a cold prefill."""
        if not self._payload_resumable(payload):
            return False
        tokens = self._payload_tokens(payload)
        if tokens is None:
            return False
        nbytes = sum(v.nbytes for v in payload.leaves.values())
        ok = self.sessions.park(sid, ParkedSession(
            tokens=tokens, extras_fp=payload.extras_fp,
            nbytes=float(nbytes), data=payload))
        if ok:
            self.journal.append(("adopt", {"sid": sid}))
        return ok

    def _park(self, slot: int, st: SeqState) -> None:
        """Park a finishing slot's state under its session id (called from
        ``_finish_slot`` while the cache rows are still intact)."""
        if not st.session or not self.sessions.enabled:
            return
        if self._slot_prompt[slot] is None:
            return  # injected without prompt tokens: nothing to match later
        payload = self._slot_payload(slot)
        tokens = self._payload_tokens(payload)
        nbytes = sum(v.nbytes for v in payload.leaves.values())
        ok = self.sessions.park(st.session, ParkedSession(
            tokens=tokens, extras_fp=self._slot_extras_fp[slot],
            nbytes=float(nbytes), data=payload))
        if ok:
            self.parks += 1
            self.journal.append(("park", {"rid": st.rid, "sid": st.session}))
            if self.on_park is not None:
                self.on_park(st.rid, st.session)

    def _warm_plan(self, job: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """Reuse plan for a waiting job: a parked session its prompt
        extends, else a stored prefix it extends; None means cold prefill.
        ``rows``/``start`` describe the cache state to re-inject;
        ``time_len`` is set when ``rows`` are seq-sliced pieces that must be
        pasted into zeroed full-capacity rows."""
        tokens = np.asarray(job["tokens"])
        cap = self.serving.max_seq
        sid = job.get("session")
        if sid and self.sessions.enabled:
            parked = self.sessions.peek(sid)
            if parked is not None and parked.extras_fp == self._job_fp(job):
                suffix = extension_suffix(parked.tokens, tokens)
                p = parked.data
                if (suffix is not None and isinstance(p, SlotPayload)
                        and self._payload_resumable(p)
                        and p.position + len(suffix) + 1 < cap):
                    # the parked rows are popped at COMMIT time (in
                    # _admit_warm_hits) — page reservation may still defer
                    # this admission, and a deferred plan must not have
                    # consumed the session
                    # cached counts the cache POSITIONS reused (vision
                    # prefix included) — the same accounting the analytic
                    # backend's context-token mirror reports
                    return {"kind": "resume", "rows": p.leaves,
                            "start": p.position, "time_len": None,
                            "suffix": suffix, "cached": p.position,
                            "sid": sid}
        if self.prefix_store.enabled:
            e = self.prefix_store.lookup(tokens, self._job_fp(job))
            if e is None:
                return None
            suffix = np.asarray(tokens[len(e.tokens):])
            if e.sliceable:
                vis = self._prompt_prefix(job["extras"])
                start = vis + len(e.tokens)
                if start + len(suffix) + 1 < cap:
                    plan = {"kind": "prefix", "rows": e.data, "start": start,
                            "time_len": start, "suffix": suffix,
                            "cached": start}
                    if self._pt is not None:
                        # paged entry: {"pages", "t_len"} — rows are
                        # gathered (and full pages CoW-shared) at admit
                        plan["rows"] = None
                        plan["pages_entry"] = e.data
                    return plan
            else:
                start = int(e.data["position"])
                rows = e.data["rows"]
                if (self._rows_compatible(rows)
                        and start + len(suffix) + 1 < cap):
                    return {"kind": "prefix", "rows": rows, "start": start,
                            "time_len": None, "suffix": suffix,
                            "cached": start}
        return None

    def _admit_warm_hits(self) -> None:
        i = 0
        while i < len(self.waiting):
            slot = self._free_slot()
            if slot is None:
                return
            job = self.waiting[i]
            plan = self._warm_plan(job)
            if plan is None:
                i += 1
                continue
            if self.pool is not None and not self._reserve_warm(job, plan):
                # starved for pages: stop admitting (FIFO within warm hits)
                # and let the fused block split at the pressure boundary
                self._page_pressure = True
                return
            if plan.get("sid"):
                self.sessions.resume(plan["sid"])  # rows consumed this turn
            del self.waiting[i]
            job["t_admit"] = time.monotonic()
            self._admit_warm(job, slot, plan)

    def _reserve_warm(self, job: Dict[str, Any],
                      plan: Dict[str, Any]) -> bool:
        """Reserve ``job``'s full page budget for a warm admission. FULL
        pages strictly behind the reused frontier are CoW-shared from the
        store entry (incref, no copy); the boundary page and the growth tail
        come from fresh pages. All-or-nothing: on failure every reference
        taken here is dropped."""
        start = int(plan["start"])
        total = min(start + len(plan["suffix"]) + int(job["max_new"]),
                    self.serving.max_seq)
        need = self._page_need(total)
        ent = plan.get("pages_entry")
        shared: List[int] = []
        if ent is not None:
            ps = self.serving.kv_page_size
            shared = [int(p) for p in ent["pages"][:int(ent["t_len"]) // ps]]
            # pin BEFORE allocating: _reserve_pages may evict this very
            # store entry under pressure, and its pages must survive
            self.pool.incref(shared)
        fresh = self._reserve_pages(need - len(shared))
        if fresh is None:
            if shared:
                self.pool.decref(shared)
            return False
        plan["_pages"] = shared + fresh
        return True

    def _admit_warm(self, job: Dict[str, Any], slot: int,
                    plan: Dict[str, Any]) -> None:
        """Copy reused cache rows into a batch-1 cache, prefill ONLY the
        suffix through the jitted decode scan, and scatter the result into
        ``slot``. ``prefill_tokens`` moves by the suffix length alone."""
        cap = self.serving.max_seq
        pages = plan.pop("_pages", None)
        if pages is not None:
            self._assign_pages(slot, pages)
        rows = plan["rows"]
        if rows is None and plan.get("pages_entry") is not None:
            # copy-free hit: the shared pages are already mapped into the
            # slot's table; gather them once into seq-sliced dense pieces
            # for the batch-1 suffix prefill below
            rows = self._gather_prefix_rows(plan["pages_entry"])
        t_len = plan["time_len"]
        start = int(plan["start"])
        suffix = np.asarray(plan["suffix"], np.int32)

        def build(path, leaf):
            name = jax.tree_util.keystr(path)
            bax, sax = self._axis_by_name[name]
            shape = leaf.shape[:bax] + leaf.shape[bax + 1:]
            row = rows.get(name)
            if row is not None and tuple(row.shape) == tuple(shape):
                # explicit COPY: cache1 is donated to the suffix-prefill
                # jit, and a store-held row must survive its admission
                # (non-sliceable prefix entries are reused across hits)
                out = jnp.array(row, leaf.dtype)
            elif row is not None:  # seq-sliced piece -> paste into zeros
                rsax = sax - (1 if sax > bax else 0)
                sl = [slice(None)] * len(shape)
                sl[rsax] = slice(0, row.shape[rsax])
                out = jnp.zeros(shape, leaf.dtype).at[tuple(sl)].set(
                    jnp.asarray(row, leaf.dtype))
            elif name == "['pos']":  # synthesized: linear placement
                pos = np.full(shape, -1, np.int32)
                pos[:t_len] = np.arange(t_len, dtype=np.int32)
                out = jnp.asarray(pos)
            elif name == "['index']":
                out = jnp.asarray(t_len % cap, jnp.int32)
            else:  # unreachable for known caches; keep the walk total
                out = jnp.zeros(shape, leaf.dtype)
            return jnp.expand_dims(out, bax)

        tmpl = (self._dense_spec_tree if self._dense_spec_tree is not None
                else self.cache)
        cache1 = jax.tree_util.tree_map_with_path(build, tmpl)
        n = len(suffix)
        total = start + n
        np_ = n
        if self._sliceable:
            # pad to the power-of-two ladder (bounds traces, like bucketed
            # prefill); pad writes land past the true end and are re-masked
            np_ = min(_next_bucket(n, lo=8), cap - start)
        toks = np.full((np_,), suffix[-1], np.int32)
        toks[:n] = suffix
        positions = start + np.arange(np_, dtype=np.int32)
        teff = (self._context_bucket(start + np_ + 1) if self._ctx_buckets
                else self.serving.max_seq)
        if self._sliceable:
            # ONE multi-token pass over the suffix (a weights pass per
            # suffix, not per token): decode_chunk writes the S new KV
            # rows and attends the reused prefix by absolute position
            batch = {"tokens": jnp.asarray(toks[None]),
                     "positions": jnp.asarray(positions[None])}
            if np_ > n:
                batch["lengths"] = jnp.asarray([n], jnp.int32)
            logits1, cache1 = self._warm_chunk(self.params, cache1, batch,
                                               teff)
            first_logits = np.asarray(logits1)[0]
        elif self._warm_chunk_recurrent is not None:
            # chunked recurrent suffix prefill: ONE pass seeding the ssd /
            # rglru chunk kernels from the cached state — a weights pass
            # per suffix instead of per token (np_ == n here: recurrent
            # state admits no padding, every token advances the scan)
            batch = {"tokens": jnp.asarray(toks[None]),
                     "positions": jnp.asarray(positions[None])}
            logits1, cache1 = self._warm_chunk_recurrent(self.params,
                                                         cache1, batch)
            first_logits = np.asarray(logits1)[0]
        else:
            # point-in-time state families step their own decode path over
            # the exact suffix (recurrent state admits no padding)
            logits_all, cache1 = self._warm_scan(self.params, cache1,
                                                 jnp.asarray(toks),
                                                 jnp.asarray(positions),
                                                 teff)
            first_logits = np.asarray(logits_all)[n - 1]
        if np_ > n and "pos" in cache1:
            cache1 = dict(cache1)
            cache1["pos"] = jnp.where(cache1["pos"] < total,
                                      cache1["pos"], -1)
            cache1["index"] = jnp.full_like(cache1["index"], total % cap)
        self._insert_cache(cache1, slot)
        self._start_seq(job, slot, total, first_logits,
                        prefill_count=n,
                        warm=(plan["kind"], int(plan["cached"])))

    def _store_prefixes(self, slot: int, job: Dict[str, Any]) -> None:
        """Deposit a just-admitted slot's cache rows into the prefix store
        at bucket-aligned prefix lengths (sliceable families) or the exact
        processed length (ssm/hybrid point-in-time state)."""
        if not self.prefix_store.enabled:
            return
        tokens = np.asarray(job["tokens"])
        vis = self._prompt_prefix(job["extras"])
        if vis + len(tokens) > self.serving.max_seq:
            return  # rolled/truncated cache rows don't map to positions
        fp = self._job_fp(job)
        store = self.prefix_store
        if not self._sliceable:
            if store.contains(tokens, fp):
                return
            rows = {name: np.asarray(jnp.take(leaf, slot, axis=bax))
                    for name, leaf, bax in self._leaf_rows()}
            nb = float(sum(r.nbytes for r in rows.values()))
            store.insert(tokens, fp, nb,
                         {"rows": rows, "position": int(vis + len(tokens))},
                         sliceable=False)
            return
        need = [L for L in prefix_buckets(len(tokens), store.min_prefix)
                if not store.contains(tokens[:L], fp)]
        if not need:
            return
        if self._pt is not None:
            # copy-free deposit: the entry is a page-id list increfing the
            # slot's own pages — no row duplication. The boundary page is
            # increfed for CONTENT liveness only (warm hits CoW-share just
            # the full pages strictly behind t_len; the depositor keeps
            # writing rows >= t_len on that page, which readers never see
            # because they slice to t_len).
            ps = self.serving.kv_page_size
            for L in need:
                t_len = vis + L
                npg = pages_needed(t_len, ps, self.serving.max_seq)
                pages = [int(p) for p in self._pt[slot][:npg]]
                self.pool.incref(pages)
                ok = store.insert(tokens[:L], fp,
                                  npg * self.pool.page_bytes,
                                  {"pages": pages, "t_len": t_len},
                                  sliceable=True)
                if not ok:
                    self.pool.decref(pages)
            return
        # rows stay device-resident: jnp.take copies out of the (donated)
        # pool and the bucket slices are device slices — depositing a
        # prefix never round-trips the KV through the host
        rows = {name: jnp.take(leaf, slot, axis=bax)
                for name, leaf, bax in self._leaf_rows()
                if name not in ("['pos']", "['index']")}
        for L in need:
            t_len = vis + L
            data = {}
            nb = 0.0
            for name, row in rows.items():
                bax, sax = self._axis_by_name[name]
                rsax = sax - (1 if sax > bax else 0)
                sl = [slice(None)] * row.ndim
                sl[rsax] = slice(0, t_len)
                piece = row[tuple(sl)]
                nb += piece.nbytes
                data[name] = piece
            store.insert(tokens[:L], fp, nb, data, sliceable=True)

    def encode_image(self, image: np.ndarray, num_patches: int = 0,
                     frontend_dim: int = 0) -> np.ndarray:
        """Encode-only entry point (executed partial offload): run the
        vision frontend on THIS tier's device and return compact patch
        embeddings in the target ``(num_patches, frontend_dim)`` geometry
        (defaults to this engine's own model).

        The stub frontend tiles the normalized pixels into the patch grid —
        bit-identical to what a fusion-local prefill would compute, so
        routing an image off the fusion tier never changes the generated
        tokens; only the compact embeddings travel."""
        with obs.span("moa.engine.encode"):
            img = np.asarray(image)
            p = num_patches or self.cfg.num_patches
            fd = frontend_dim or self.cfg.frontend_dim
            key = (p, fd, int(img.size))
            fn = self._encode_jits.get(key)
            if fn is None:
                need = p * fd
                rep = max(1, int(np.ceil(need / max(img.size, 1))))

                def _enc(x):
                    flat = x.reshape(-1).astype(jnp.float32) / 255.0
                    return jnp.tile(flat, rep)[:need].reshape(p, fd)

                fn = jax.jit(_enc)
                self._encode_jits[key] = fn
            out = np.asarray(fn(jnp.asarray(img)))
            self.encode_tokens += p
            self.journal.append(("encode", {"patches": p}))
            return out

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    # -- paged KV pool ------------------------------------------------------

    def _page_need(self, total_rows: int) -> int:
        """Pages a request occupying ``total_rows`` cache rows reserves.
        Recurrent families charge a fixed per-slot amount (their state has
        no positional pages)."""
        if not self._sliceable:
            return self._slot_page_charge
        return pages_needed(total_rows, self.serving.kv_page_size,
                            self.serving.max_seq)

    def _reserve_pages(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` fresh pages, reclaiming prefix-store LRU entries
        under pressure (store-held pages are spare capacity: a live request
        always beats a cached prefix). None when the pool is truly short."""
        if n <= 0:
            return []
        pool = self.pool
        if not pool.can_alloc(n) and self._pt is not None:
            while not pool.can_alloc(n):
                if self.prefix_store.evict_oldest() is None:
                    break
        return pool.alloc(n)

    def _assign_pages(self, slot: int, pages: List[int]) -> None:
        """Record ``slot``'s page list and host page table. The DEVICE page
        table row is written by the insert path that follows (prefill
        scatter / batch-1 insert / migration install)."""
        self._slot_pages[slot] = list(pages)
        if self._pt is not None:
            row = np.zeros((self._n_pt,), np.int32)
            row[:len(pages)] = pages
            self._pt[slot] = row

    def _release_pages(self, slot: int) -> None:
        """Drop ``slot``'s page references (pages whose last reader this was
        rejoin the free list) and retarget its DEVICE page table at the null
        page: a freed slot keeps stepping inside the fused block, and its
        dead writes must never land on a page that may be re-allocated."""
        if self.pool is None:
            return
        if self._slot_pages[slot]:
            self.pool.decref(self._slot_pages[slot])
            self._slot_pages[slot] = []
        if self._pt is not None:
            self._pt[slot] = 0
            cache = dict(self.cache)
            cache["pages"] = cache["pages"].at[slot].set(
                jnp.zeros((self._n_pt,), jnp.int32))
            self.cache = cache

    def _on_prefix_evict(self, entry) -> None:
        """Store removal hook: decref a paged entry's shared pages."""
        data = getattr(entry, "data", None)
        if isinstance(data, dict) and "pages" in data:
            self.pool.decref(data["pages"])

    def _gather_slot_rows(self, slot: int) -> Dict[str, Any]:
        """Dense-geometry per-slot rows gathered THROUGH the page table —
        the paged engine's side of the (unchanged) migration wire format.
        Rows past the written frontier come from the null page; their
        positions are -1, so they are masked wherever they land."""
        n_pt, page = self._n_pt, self.serving.kv_page_size
        pt = jnp.asarray(self._pt[slot])
        rows = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.cache):
            name = jax.tree_util.keystr(path)
            if name == "['pages']":
                continue
            bax = self._axis_by_name[name][0]
            if name in self._paged_names:
                g = jnp.take(leaf, pt, axis=bax)  # (..., n_pt, page, ...)
                rows[name] = g.reshape(g.shape[:bax] + (n_pt * page,)
                                       + g.shape[bax + 2:])
            else:
                rows[name] = jnp.take(leaf, slot, axis=bax)
        return rows

    def _gather_prefix_rows(self, ent: Dict[str, Any]) -> Dict[str, Any]:
        """Materialize a paged prefix-store entry ({"pages", "t_len"}) into
        the seq-sliced dense pieces the warm-admission build step pastes —
        the deposit itself was copy-free (page increfs, no row copies)."""
        page = self.serving.kv_page_size
        t_len = int(ent["t_len"])
        npg = len(ent["pages"])
        pt = jnp.asarray(np.asarray(ent["pages"], np.int32))
        rows = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.cache):
            name = jax.tree_util.keystr(path)
            if name not in self._paged_names:
                continue
            bax = self._axis_by_name[name][0]
            g = jnp.take(leaf, pt, axis=bax)
            g = g.reshape(g.shape[:bax] + (npg * page,) + g.shape[bax + 2:])
            rows[name] = g[(slice(None),) * bax + (slice(0, t_len),)]
        return rows

    def _install_rows(self, slot: int, rows: Dict[str, Any]) -> None:
        """Scatter dense-geometry per-slot rows into ``slot`` (through the
        page table on paged engines). Inverse of ``_gather_slot_rows``."""
        if self._pt is None:
            def put(path, leaf, bax):
                row = rows[jax.tree_util.keystr(path)]
                idx = (slice(None),) * bax + (slot,)
                return leaf.at[idx].set(jnp.asarray(row))

            self.cache = jax.tree_util.tree_map_with_path(
                put, self.cache, self._cache_batch_axis)
            return
        n_pt, page = self._n_pt, self.serving.kv_page_size
        pt = jnp.asarray(self._pt[slot])
        pool = dict(self.cache)
        pages_leaf = pool.pop("pages")

        def put(path, leaf):
            name = jax.tree_util.keystr(path)
            row = jnp.asarray(rows[name])
            bax = self._axis_by_name[name][0]
            if name in self._paged_names:
                row = row.reshape(row.shape[:bax] + (n_pt, page)
                                  + row.shape[bax + 1:])
                idx = (slice(None),) * bax + (pt,)
            else:
                idx = (slice(None),) * bax + (slot,)
            return leaf.at[idx].set(row.astype(leaf.dtype))

        pool = jax.tree_util.tree_map_with_path(put, pool)
        pool["pages"] = pages_leaf.at[slot].set(pt)
        self.cache = pool

    def kv_gauges(self) -> Dict[str, float]:
        """KV occupancy gauges the scheduler observes: pages_total / free /
        shared + high-water. Dense engines synthesize slot-granular numbers
        so both pool designs report comparable headroom."""
        if self.pool is not None:
            return self.pool.gauges()
        npp = max(1, -(-self.serving.max_seq // self.serving.kv_page_size))
        used = sum(1 for s in self.slots if s is not None)
        self._slots_hw = max(self._slots_hw, used)
        return {"pages_total": len(self.slots) * npp,
                "pages_free": (len(self.slots) - used) * npp,
                "pages_shared": 0,
                "pages_high_water": self._slots_hw * npp,
                "page_bytes": 0.0}

    def kv_headroom(self) -> float:
        """Free fraction of the KV pool in [0, 1]."""
        g = self.kv_gauges()
        return g["pages_free"] / max(1, g["pages_total"])

    def _insert_cache(self, cache1, slot: int) -> None:
        """Copy a batch-1 prefill cache into slot ``slot`` (through the
        page table on paged engines — shared CoW pages receive the very
        bytes that were gathered out of them, unreserved tail entries land
        on the null page)."""
        if self._pt is not None:
            n_pt, page = self._n_pt, self.serving.kv_page_size
            pt = jnp.asarray(self._pt[slot])
            pool = dict(self.cache)
            pages_leaf = pool.pop("pages")

            def insp(path, pool_leaf, one):
                name = jax.tree_util.keystr(path)
                bax = self._axis_by_name[name][0]
                row = one[(slice(None),) * bax + (0,)]
                if name in self._paged_names:
                    row = row.reshape(row.shape[:bax] + (n_pt, page)
                                      + row.shape[bax + 1:])
                    idx = (slice(None),) * bax + (pt,)
                else:
                    idx = (slice(None),) * bax + (slot,)
                return pool_leaf.at[idx].set(row.astype(pool_leaf.dtype))

            pool = jax.tree_util.tree_map_with_path(insp, pool, cache1)
            pool["pages"] = pages_leaf.at[slot].set(pt)
            self.cache = pool
            return

        def ins(pool, one, bax):
            idx = (slice(None),) * bax + (slot,)
            sel = (slice(None),) * bax + (0,)
            return pool.at[idx].set(one[sel].astype(pool.dtype))
        self.cache = jax.tree.map(ins, self.cache, cache1,
                                  self._cache_batch_axis)

    def _start_seq(self, job: Dict[str, Any], slot: int, prompt_len: int,
                   first_logits: np.ndarray,
                   prefill_count: Optional[int] = None,
                   warm: Optional[tuple] = None) -> None:
        """Shared admit bookkeeping: first token, done-check, journal.
        ``prefill_count`` overrides the prefill-token charge (a warm admit
        prefilled only its suffix); ``warm`` = (kind, cached_tokens) tags
        prefix-hit / resumed-session admissions."""
        st = SeqState(rid=job["rid"], prompt_len=prompt_len,
                      max_new=job["max_new"], t_submit=job["t"],
                      session=job.get("session"))
        self._slot_prompt[slot] = np.asarray(job["tokens"], np.int32)
        self._slot_extras_fp[slot] = (
            self._job_fp(job)
            if (st.session and self.sessions.enabled)
            or self.prefix_store.enabled else b"")
        first = self._sample(first_logits)
        st.generated.append(int(first))
        st.t_first_token = time.monotonic()
        self.slots[slot] = st
        self.positions[slot] = prompt_len
        charged = prompt_len if prefill_count is None else prefill_count
        self.prefill_tokens += charged
        self.decode_tokens += 1
        if warm is not None:
            kind, cached = warm
            if kind == "resume":
                self.resumed_sessions += 1
                self.resumed_tokens += cached
            else:
                self.prefix_hits += 1
                self.prefix_hit_tokens += cached
            self.journal.append(("warm", {"rid": st.rid, "kind": kind,
                                          "cached": cached,
                                          "suffix": charged}))
            if self.on_warm is not None:
                self.on_warm(st.rid, kind, cached, charged)
        self.journal.append(("admit", {"rid": st.rid, "slot": slot}))
        if self.on_admit is not None:
            self.on_admit(st.rid, job.get("t_admit", st.t_first_token),
                          st.t_first_token)
        if self.on_token is not None:
            self.on_token(st.rid, int(first), st.t_first_token)
        self._store_prefixes(slot, job)
        # a request may be complete straight out of prefill (EOS first
        # token, max_new == 1, or a prompt already at capacity)
        if (first == self.eos_id or len(st.generated) >= st.max_new
                or prompt_len + 1 >= self.serving.max_seq):
            self._finish_slot(slot, time.monotonic())

    def _finish_slot(self, slot: int, now: float) -> None:
        st = self.slots[slot]
        st.done = True
        st.t_done = now
        self._park(slot, st)  # while the slot's cache rows are intact
        self.finished.append(st)
        self.journal.append(("finish", {"rid": st.rid}))
        self.slots[slot] = None
        self._release_pages(slot)

    def _prompt_prefix(self, extras: Dict[str, Any]) -> int:
        if self.cfg.frontend == "vision_stub" and "patches" in extras:
            return self.cfg.num_patches
        return 0

    # -- cross-tier speculative decoding (draft-and-verify) -----------------
    #
    # The cluster runtime drives one verify loop per speculated request:
    #
    #   target: submit + _admit (a NORMAL slot)    draft: spec_admit_quiet
    #   target: spec_begin (trim pages to the written frontier)
    #   loop:   draft.spec_draft(k) -> target.spec_verify(block)
    #           -> draft.spec_sync(committed)
    #   target: spec_release (restore the eager full-budget reservation
    #           before the slot returns to the fused step() path)
    #
    # spec_verify feeds [pending, d_1..d_k] through ONE chunked decode with
    # per-position logits, samples the target's OWN token at every position
    # under the slot's key stream (one split per COMMITTED token — the
    # fused path's per-step math), commits the longest prefix on which the
    # draft agreed plus the target's correction token, and rolls the cache
    # back past the first mismatch (dense: pos/index rewind before the
    # batch-1 insert; recurrent: re-feed the committed prefix from the
    # untouched pre-verify rows; paged: decref the speculative tail pages).
    # The committed stream is BY CONSTRUCTION the target-only stream, so
    # speculation changes latency, never output.

    def _make_spec_draft(self):
        """K autoregressive decode steps on a BATCH-1 cache copy — the
        draft side of speculation. Same per-step split/sample math as the
        fused block; the cache copy is discarded, so proposing never
        mutates the draft slot (only verified commits do, via spec_sync)."""
        model = self.model
        temp = float(self.temp)
        max_seq = int(self.serving.max_seq)

        def draft(params, cache1, key, tok, pos, teff, k):
            ctx = teff if teff < max_seq else None

            def body(carry, _):
                cache1, key, tok, pos = carry
                logits, cache2 = model.decode_step(
                    params, cache1,
                    {"tokens": tok[None, None], "positions": pos[None]},
                    ctx=ctx)
                if temp > 0:
                    key, sub = jax.random.split(key, 2)
                    nxt = jax.random.categorical(sub, logits[0] / temp)
                else:
                    nxt = jnp.argmax(logits[0], axis=-1)
                nxt = nxt.astype(jnp.int32)
                return (cache2, key, nxt, pos + 1), nxt

            (_, key, *_), toks = jax.lax.scan(
                body, (cache1, key, jnp.asarray(tok, jnp.int32),
                       jnp.asarray(pos, jnp.int32)), None, length=k)
            return toks, key

        return draft

    def _ensure_spec_chunk(self):
        if self._spec_chunk_fn is not None:
            return self._spec_chunk_fn
        model, max_seq = self.model, self.serving.max_seq
        if self._sliceable:
            fn = lambda p, c, b, teff: model.decode_chunk(
                p, c, b, ctx=(teff if teff < max_seq else None),
                all_logits=True)
        else:
            fn = lambda p, c, b, teff: model.decode_chunk_recurrent(
                p, c, b, all_logits=True)
        self._spec_chunk_fn = jax.jit(fn, donate_argnums=(1,),
                                      static_argnums=(3,))
        return self._spec_chunk_fn

    def _spec_cache1(self, slot: int):
        """Batch-1 cache holding fresh COPIES of ``slot``'s rows (gathered
        through the page table on paged engines) — safe to donate to the
        verify/draft jits; the slot itself is untouched until an explicit
        ``_insert_cache``."""
        if self._pt is not None:
            rows = self._gather_slot_rows(slot)
        else:
            rows = {name: jnp.take(leaf, slot, axis=bax)
                    for name, leaf, bax in self._leaf_rows()}
        tmpl = (self._dense_spec_tree if self._dense_spec_tree is not None
                else self.cache)

        def build(path, leaf):
            name = jax.tree_util.keystr(path)
            bax = self._axis_by_name[name][0]
            return jnp.expand_dims(rows[name].astype(leaf.dtype), bax)

        return jax.tree_util.tree_map_with_path(build, tmpl)

    def spec_slot(self, rid: int) -> Optional[int]:
        """Slot currently serving ``rid`` (None: queued/finished/unknown)."""
        for i, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                return i
        return None

    def _spec_resize_pages(self, slot: int, rows: int) -> bool:
        """Resize ``slot``'s page reservation to exactly cover ``rows``
        cache rows. The verify loop grows ahead of each chunk and shrinks
        back to the committed frontier afterwards — the decref IS the
        rejected speculative tail's release. Only pages strictly beyond
        the written frontier ever trim, so CoW pages shared with the
        prefix store (always behind the frontier) are never touched.
        Returns False when the pool cannot grow."""
        if self._pt is None:
            return True  # dense engine / recurrent flat charge: no-op
        need = self._page_need(min(int(rows), self.serving.max_seq))
        have = self._slot_pages[slot]
        if need > len(have):
            fresh = self._reserve_pages(need - len(have))
            if fresh is None:
                return False
            have.extend(fresh)
        elif need < len(have):
            tail = have[need:]
            del have[need:]
            self.pool.decref(tail)
        row = np.zeros((self._n_pt,), np.int32)
        row[:len(have)] = have
        self._pt[slot] = row
        cache = dict(self.cache)
        cache["pages"] = cache["pages"].at[slot].set(jnp.asarray(row))
        self.cache = cache
        # allocator invariants: rejected-tail pages really came back, the
        # table matches the reservation, and every pool page is free XOR
        # referenced (refcount leaks fail loudly here, not at eviction)
        assert len(self._slot_pages[slot]) == need, (
            f"slot {slot}: reservation {len(self._slot_pages[slot])} != "
            f"needed {need} pages")
        self.pool.check()
        return True

    def spec_admit_quiet(self, rid: int, tokens: np.ndarray, max_new: int,
                         extras: Optional[Dict[str, Any]] = None
                         ) -> Optional[int]:
        """Admit a DRAFT-side shadow of a speculated request: a normal slot
        (single-job legacy prefill path) admitted with the streaming hooks
        muted — the target's hooks are the request's real event stream and
        the runtime must not see admit/token events twice. Returns the
        slot, or None when admission failed (no slot / no pages / finished
        straight out of prefill), in which case no trace remains."""
        slot = self._free_slot()
        if slot is None:
            return None
        job = {"rid": int(rid), "tokens": np.asarray(tokens),
               "max_new": int(max_new), "extras": extras or {},
               "deadline": None, "session": None, "t": time.monotonic()}
        if self.pool is not None:
            vis = self._prompt_prefix(job["extras"])
            total = min(vis + len(job["tokens"]) + int(max_new),
                        self.serving.max_seq)
            pages = self._reserve_pages(self._page_need(total))
            if pages is None:
                return None
            self._assign_pages(slot, pages)
        hooks = (self.on_admit, self.on_token, self.on_warm, self.on_park)
        self.on_admit = self.on_token = self.on_warm = self.on_park = None
        try:
            toks = job["tokens"][None]
            batch = {"tokens": jnp.asarray(toks, jnp.int32)}
            for k, v in job["extras"].items():
                batch[k] = jnp.asarray(v)[None]
            logits, cache1 = self._prefill1(self.params, batch)
            self._insert_cache(cache1, slot)
            prefix = self._prompt_prefix(job["extras"])
            self._start_seq(job, slot, toks.shape[1] + prefix,
                            np.asarray(logits)[0])
        finally:
            self.on_admit, self.on_token, self.on_warm, self.on_park = hooks
        st = self.slots[slot]
        if st is None or st.rid != rid:
            # finished straight out of prefill (EOS / budget / cap): a
            # draft shadow has no consumer — drop the finished record
            self.finished = [f for f in self.finished if f.rid != rid]
            return None
        self.journal.append(("spec_admit", {"rid": rid, "slot": slot}))
        return slot

    def spec_set_pending(self, rid: int, token: int) -> None:
        """Overwrite the draft slot's pending (last sampled, not yet in
        cache) token with the TARGET's — after admission and after every
        verify round the draft must continue from what the target actually
        committed, not from its own independent sample."""
        slot = self.spec_slot(rid)
        if slot is None:
            return
        self.slots[slot].generated[-1] = int(token) % self.cfg.vocab_size

    def spec_begin(self, rid: int) -> bool:
        """Start speculating on a target slot: trim the eager full-budget
        page reservation down to the written frontier so verify rounds can
        grow/shrink page-exactly. Balanced by ``spec_release``."""
        slot = self.spec_slot(rid)
        if slot is None:
            return False
        self._spec_resize_pages(slot, int(self.positions[slot]))
        self.journal.append(("spec_begin", {"rid": rid}))
        return True

    def spec_release(self, rid: int) -> None:
        """Stop speculating: restore the slot's eager full-budget page
        reservation (remaining decode + the pending token's row) so the
        fused ``step()`` path can run it to completion without mid-decode
        page faults — its writes assume the admission-time reservation."""
        slot = self.spec_slot(rid)
        if slot is None:
            return
        st = self.slots[slot]
        total = min(int(self.positions[slot])
                    + max(0, st.max_new - len(st.generated)) + 1,
                    self.serving.max_seq)
        ok = self._spec_resize_pages(slot, total)
        # the verify loop only ever GREW past the frontier with pages it
        # returns before anyone else allocates, so the regrow cannot starve
        assert ok, f"spec_release could not restore rid {rid}'s reservation"
        self.journal.append(("spec_release", {"rid": rid}))

    def spec_draft(self, rid: int, k: int) -> Optional[np.ndarray]:
        """Propose ``k`` tokens for ``rid`` by running the batch-1 draft
        scan on a COPY of the slot's cache. The slot itself (rows,
        position, generated) is not advanced — ``spec_sync`` does that once
        the target reports what it committed."""
        slot = self.spec_slot(rid)
        if slot is None:
            return None
        st = self.slots[slot]
        p = int(self.positions[slot])
        k = min(int(k), self.serving.max_seq - 1 - p)
        if k <= 0:
            return None
        cache1 = self._spec_cache1(slot)
        teff = (self._context_bucket(p + k + 1) if self._ctx_buckets
                else self.serving.max_seq)
        if self._spec_draft_fn is None:
            self._spec_draft_fn = jax.jit(self._make_spec_draft(),
                                          donate_argnums=(1,),
                                          static_argnums=(5, 6))
        toks, key2 = self._spec_draft_fn(
            self.params, cache1, self._keys[slot], int(st.generated[-1]),
            p, teff, k)
        if self.temp > 0:
            self._keys = self._keys.at[slot].set(key2)
        self.drafted_tokens += k
        self.journal.append(("spec_draft", {"rid": rid, "k": k}))
        return np.asarray(toks)

    def spec_verify(self, rid: int, draft) -> Optional[Dict[str, Any]]:
        """Verify a draft block against this TARGET slot in one chunked
        forward. Feeds ``[pending, d_1..d_k]`` at positions ``p..p+k``,
        samples the target's own token at every position under the slot's
        key stream (one split per COMMITTED token — exactly what the fused
        path would have consumed, so a rejected draft never desyncs the
        stream), commits the agreeing prefix + the correction token, and
        rolls back everything past the first mismatch. Returns the round's
        bookkeeping, or None when the slot is gone / nothing can verify."""
        slot = self.spec_slot(rid)
        if slot is None:
            return None
        st = self.slots[slot]
        p = int(self.positions[slot])
        draft = [int(x) for x in np.asarray(draft).reshape(-1)]
        k = min(len(draft), self.serving.max_seq - 1 - p)
        if k <= 0:
            return None
        draft = draft[:k]
        s = k + 1
        while s > 1 and not self._spec_resize_pages(slot, p + s):
            k -= 1
            s -= 1
            draft = draft[:k]  # pool-starved: verify a shorter block
        if not self._spec_resize_pages(slot, p + s):
            return None
        cache1 = self._spec_cache1(slot)
        toks = np.asarray([int(st.generated[-1])] + draft, np.int32)
        poss = p + np.arange(s, dtype=np.int32)
        batch = {"tokens": jnp.asarray(toks[None]),
                 "positions": jnp.asarray(poss[None])}
        teff = (self._context_bucket(p + s + 1) if self._ctx_buckets
                else self.serving.max_seq)
        chunk = self._ensure_spec_chunk()
        logits_all, cache2 = chunk(self.params, cache1, batch, teff)
        logits_all = np.asarray(logits_all)[0]  # (s, V)
        cap = self.serving.max_seq
        key = self._keys[slot]
        commits: List[int] = []
        finished = False
        for i in range(s):
            if self.temp > 0:
                key, sub = jax.random.split(key, 2)
                nxt = int(jax.random.categorical(
                    sub, jnp.asarray(logits_all[i]) / self.temp))
            else:
                nxt = int(np.argmax(logits_all[i]))
            commits.append(nxt)
            if (nxt == self.eos_id
                    or len(st.generated) + len(commits) >= st.max_new
                    or p + len(commits) + 1 >= cap):
                finished = True
                break
            if i < k and draft[i] != nxt:
                break  # first mismatch: the tail is dead
        m = len(commits)  # cache rows now valid: [pending] + accepted
        commit_end = p + m
        if self._sliceable:
            # rewind BEFORE the insert, mirroring the warm-admission pad
            # re-mask: rejected rows read as holes, the write index resumes
            # at the committed frontier
            cache2 = dict(cache2)
            cache2["pos"] = jnp.where(cache2["pos"] < commit_end,
                                      cache2["pos"], -1)
            cache2["index"] = jnp.full_like(cache2["index"],
                                            commit_end % cap)
            self._insert_cache(cache2, slot)
        elif m == s:
            self._insert_cache(cache2, slot)  # nothing to rewind
        else:
            # recurrent state is a point-in-time snapshot — it cannot
            # rewind. Re-feed ONLY the committed prefix from the slot's
            # untouched pre-verify rows (the donated cache1 was a copy).
            redo = self._spec_cache1(slot)
            rb = {"tokens": jnp.asarray(toks[None, :m]),
                  "positions": jnp.asarray(poss[None, :m])}
            _, redo = chunk(self.params, redo, rb, teff)
            self._insert_cache(redo, slot)
        if self.temp > 0:
            self._keys = self._keys.at[slot].set(self._put(key))
        self.positions[slot] = commit_end
        self._spec_resize_pages(slot, commit_end)  # decref rejected tail
        self.accepted_tokens += m - 1
        self.spec_rounds += 1
        self.last_heartbeat = time.monotonic()
        self.journal.append(("spec_verify", {"rid": rid, "drafted": k,
                                             "accepted": m - 1,
                                             "rolled_back": s - m}))
        # commit bookkeeping token-by-token with the step() stop rules —
        # committed tokens are real decode output (counters, streaming
        # hooks, finish), the rolled-back tail never counts toward
        # decode_tokens or max_new
        now = time.monotonic()
        for j, tok in enumerate(commits, start=1):
            if self.slots[slot] is not st:
                break  # a callback cancelled/finished the request
            st.generated.append(tok)
            self.decode_tokens += 1
            if self.on_token is not None:
                self.on_token(st.rid, tok, now)
            if (tok == self.eos_id or len(st.generated) >= st.max_new
                    or p + j + 1 >= cap):
                self._finish_slot(slot, now)
                break
        done = finished or self.slots[slot] is not st
        return {"committed": commits, "accepted": m - 1, "drafted": k,
                "rolled_back": s - m, "finished": done}

    def spec_sync(self, rid: int, committed: List[int]) -> bool:
        """Draft-side absorb of one verify round: replay the target's
        committed tokens into the draft cache in one chunk (the old pending
        token + all but the last commit), making the final commit the new
        pending token. The rejected tail was never installed here, so
        nothing rewinds. Returns False when the draft cache is out of room
        (caller stops speculating)."""
        slot = self.spec_slot(rid)
        if slot is None:
            return False
        st = self.slots[slot]
        committed = [int(t) % self.cfg.vocab_size for t in committed]
        m = len(committed)
        if m == 0:
            return True
        p = int(self.positions[slot])
        if p + m + 1 >= self.serving.max_seq:
            return False
        feed = np.asarray([int(st.generated[-1])] + committed[:-1], np.int32)
        cache1 = self._spec_cache1(slot)
        poss = p + np.arange(m, dtype=np.int32)
        batch = {"tokens": jnp.asarray(feed[None]),
                 "positions": jnp.asarray(poss[None])}
        teff = (self._context_bucket(p + m + 1) if self._ctx_buckets
                else self.serving.max_seq)
        if self._sliceable:
            _, cache1 = self._warm_chunk(self.params, cache1, batch, teff)
        elif self._warm_chunk_recurrent is not None:
            _, cache1 = self._warm_chunk_recurrent(self.params, cache1,
                                                   batch)
        else:
            _, cache1 = self._ensure_spec_chunk()(self.params, cache1,
                                                  batch, teff)
        self._insert_cache(cache1, slot)
        st.generated.extend(committed)
        self.positions[slot] = p + m
        self.journal.append(("spec_sync", {"rid": rid, "tokens": m}))
        return True

    # -- admission ----------------------------------------------------------

    def _admit(self) -> None:
        with obs.span("moa.engine.admit"):
            # page pressure is re-evaluated every admission pass: pages
            # freed by finished slots (or store eviction) clear it, a
            # starved reservation below re-raises it and the next fused
            # block splits early
            self._page_pressure = False
            if any(j.get("deadline") is not None for j in self.waiting):
                # EDF admission: earliest deadline first, FIFO among ties /
                # deadline-free requests (stable sort keeps submit order)
                self.waiting.sort(key=lambda j: (
                    j["deadline"] if j.get("deadline") is not None
                    else float("inf"), j["t"]))
            # warm admissions first (EDF order within them): a prompt
            # extending a parked session or a stored prefix copies the
            # cached rows and prefills only its suffix; everything else
            # falls through cold
            if self.waiting and (self.sessions.enabled
                                 or self.prefix_store.enabled):
                self._admit_warm_hits()
            if self.fused_steps <= 1 or not self.serving.bucket_prefill:
                self._admit_legacy()
            else:
                self._admit_bucketed()

    def _admit_legacy(self) -> None:
        while self.waiting:
            slot = self._free_slot()
            if slot is None:
                return
            job = self.waiting[0]  # peek: only admitted once pages reserve
            if self.pool is not None:
                vis = self._prompt_prefix(job["extras"])
                total = min(vis + len(job["tokens"]) + int(job["max_new"]),
                            self.serving.max_seq)
                pages = self._reserve_pages(self._page_need(total))
                if pages is None:
                    self._page_pressure = True
                    return
                self._assign_pages(slot, pages)
            job = self.waiting.pop(0)
            job["t_admit"] = time.monotonic()
            toks = job["tokens"][None]  # (1, S)
            batch = {"tokens": jnp.asarray(toks, jnp.int32)}
            for k, v in job["extras"].items():
                batch[k] = jnp.asarray(v)[None]
            logits, cache1 = self._prefill1(self.params, batch)
            self._insert_cache(cache1, slot)
            prefix = self._prompt_prefix(batch)
            self._start_seq(job, slot, toks.shape[1] + prefix,
                            np.asarray(logits)[0])

    def _admit_bucketed(self) -> None:
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free or not self.waiting:
            return
        jobs = self.waiting[:len(free)]
        if self.pool is not None:
            # eager reservation: each job reserves its FULL page budget
            # (prompt + max_new, capped) up front, so an admitted request
            # can always run to completion — no mid-decode page faults, no
            # deadlock. Starved jobs stay queued; the fused block splits at
            # the pressure boundary and re-admits the moment pages free.
            admitted = []
            for job in jobs:
                vis = self._prompt_prefix(job["extras"])
                total = min(vis + len(job["tokens"]) + int(job["max_new"]),
                            self.serving.max_seq)
                pages = self._reserve_pages(self._page_need(total))
                if pages is None:
                    self._page_pressure = True
                    break
                job["_pages"] = pages
                admitted.append(job)
            jobs = admitted
            if not jobs:
                return
        del self.waiting[:len(jobs)]
        now = time.monotonic()
        for job in jobs:
            job["t_admit"] = now
        pad_ok = self.cfg.family in _PADDED_FAMILIES
        groups: Dict[tuple, List[dict]] = {}
        for j, job in enumerate(jobs):
            n = len(job["tokens"])
            prefix = self._prompt_prefix(job["extras"])
            if pad_ok:
                bucket = min(_next_bucket(n),
                             max(n, self.serving.max_seq - prefix))
            else:
                bucket = n  # exact-length groups (ssm / hybrid)
            sig = (bucket, tuple(sorted(
                (k, np.asarray(v).shape, str(np.asarray(v).dtype))
                for k, v in job["extras"].items())))
            if self.cfg.family == "moe":
                sig += (j,)  # one group per request (see _PADDED_FAMILIES)
            groups.setdefault(sig, []).append(job)
        for sig, grp in groups.items():
            slots = [free.pop(0) for _ in grp]
            self._admit_group(grp, slots, sig[0])

    def _admit_group(self, jobs: List[dict], slots: List[int],
                     bucket: int) -> None:
        r = len(jobs)
        # pad the row count to a power of two as well (bounds retraces to
        # O(log buckets * log max_batch)); pad rows replicate row 0 and
        # scatter to the same slot, so the duplicate writes are identical
        rp = _next_bucket(r, lo=1)
        toks = np.zeros((rp, bucket), np.int32)
        lengths = np.zeros((rp,), np.int32)
        for i in range(rp):
            job = jobs[i] if i < r else jobs[0]
            n = len(job["tokens"])
            toks[i, :n] = job["tokens"]
            lengths[i] = n
        prefix = self._prompt_prefix(jobs[0]["extras"])
        with obs.span("moa.engine.prefill", tier=self.tier,
                      lengths=lengths[:r], patches=prefix, bucket=bucket):
            batch: Dict[str, Any] = {"tokens": jnp.asarray(toks)}
            for k in jobs[0]["extras"]:
                batch[k] = jnp.asarray(np.stack(
                    [np.asarray((jobs[i] if i < r else jobs[0])["extras"][k])
                     for i in range(rp)]))
            if int(lengths.min()) < bucket:  # any row right-padded
                batch["lengths"] = jnp.asarray(lengths)
            total = lengths + prefix
            for job, slot in zip(jobs, slots):
                pages = job.pop("_pages", None)
                if pages is not None:
                    self._assign_pages(slot, pages)
            slots_arr = np.asarray(slots + [slots[0]] * (rp - r), np.int32)
            if self._pt is not None:
                # pad rows replicate row 0's page table too — their
                # duplicate scatters write identical content to the same
                # pages
                pt_rows = self._pt[slots_arr]
                npg = max(1, max(len(self._slot_pages[s]) for s in slots))
                logits, self.cache = self._prefill_insert(
                    self.params, self.cache, batch, jnp.asarray(slots_arr),
                    jnp.asarray(total), jnp.asarray(pt_rows), npg)
            else:
                logits, self.cache = self._prefill_insert(
                    self.params, self.cache, batch, jnp.asarray(slots_arr),
                    jnp.asarray(total))
            with obs.span("moa.engine.prefill.read"):
                logits = np.asarray(logits)  # one host sync per group
        for i, (job, slot) in enumerate(zip(jobs, slots)):
            self._start_seq(job, slot, int(total[i]), logits[i])

    # -- sampling / stepping ------------------------------------------------

    def _sample(self, logits: np.ndarray) -> int:
        if self.temp <= 0:
            return int(np.argmax(logits))
        z = logits / self.temp
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    def _throttle_sleep(self, t_in: float) -> None:
        if self.throttle > 1.0:
            # the sleep is capped per step: an outlier step duration is a
            # compile or a host deschedule, not model compute — stretching
            # it (throttle - 1)x would amplify a one-off stall into a
            # multi-second outage of the emulated-slow node
            time.sleep(min((self.throttle - 1.0)
                           * max(0.0, time.monotonic() - t_in),
                           self.throttle_sleep_cap_s))

    def step(self) -> int:
        """Admit + one decode block for all active slots. Returns #active."""
        with obs.span("moa.engine.step"):
            t_in = time.monotonic()
            self._admit()
            active = [i for i, s in enumerate(self.slots) if s is not None]
            if not active:
                return 0
            if self.fused_steps <= 1:
                n = self._step_legacy(active)
                self._throttle_sleep(t_in)
                return n
            b = len(self.slots)
            tokens = np.zeros((b,), np.int32)
            positions = np.zeros((b,), np.int32)
            budgets = np.zeros((b,), np.int32)
            for i in active:
                st = self.slots[i]
                tokens[i] = st.generated[-1]
                positions[i] = self.positions[i]
                budgets[i] = max(0, st.max_new - len(st.generated))
            k = self.fused_steps
            if self.pool is not None and self._page_pressure and self.waiting:
                # continuous admission under page pressure: split the fused
                # block at the earliest point a slot can finish (and free
                # its pages), so starved requests admit mid-block instead
                # of waiting out a full K steps. k is a jit-static arg —
                # each distinct split length is one cached trace on the
                # power-of-two budget ladder of remaining tokens
                rem = min(max(1, self.slots[i].max_new
                              - len(self.slots[i].generated))
                          for i in active)
                k = max(1, min(k, rem))
            teff = self.serving.max_seq
            if self._ctx_buckets:
                # smallest bucket covering every position the block can
                # write; ladder = {2^n, 1.5*2^n} so the attended width
                # tracks the live context within ~33% (each bucket is one
                # cached trace)
                teff = self._context_bucket(int(positions.max()) + k + 1)
            pages = {}
            if self._pt is not None and obs.enabled():
                grid, live = block_pages(
                    positions, budgets, k, teff, self.serving.kv_page_size,
                    self.serving.max_seq)
                pages = {"pages_grid": grid, "pages_live": live}
            with obs.span("moa.engine.block", tier=self.tier,
                          positions=positions, budgets=budgets, k=k,
                          teff=teff, **pages):
                block, self.cache, self._keys = self._fused(
                    self.params, self.cache, self._keys, jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(budgets), teff, k)
                with obs.span("moa.engine.block.read"):
                    # the ONLY host sync: (B, K) per K tokens
                    block = np.asarray(block)
            now = time.monotonic()
            with obs.span("moa.engine.emit"):
                for i in active:
                    st = self.slots[i]
                    if st is None:
                        continue  # cancelled mid-block by an on_token hook
                    for j in range(k):
                        nxt = int(block[i, j])
                        st.generated.append(nxt)
                        self.decode_tokens += 1
                        self.positions[i] += 1
                        if self.on_token is not None:
                            self.on_token(st.rid, nxt, now)
                        hit_cap = self.positions[i] + 1 >= self.serving.max_seq
                        if (nxt == self.eos_id
                                or len(st.generated) >= st.max_new
                                or hit_cap):
                            self._finish_slot(i, now)
                            break
            self.steps += 1
            self.last_heartbeat = now
            self._throttle_sleep(t_in)
            return len(active)

    def _step_legacy(self, active: List[int]) -> int:
        """Per-token path: one dispatch + one logits transfer per token."""
        b = len(self.slots)
        tokens = np.zeros((b, 1), np.int32)
        positions = np.zeros((b,), np.int32)
        for i in active:
            tokens[i, 0] = self.slots[i].generated[-1]
            positions[i] = self.positions[i]
        logits, self.cache = self._decode(
            self.params, self.cache,
            {"tokens": jnp.asarray(tokens),
             "positions": jnp.asarray(positions)})
        logits = np.asarray(logits)
        now = time.monotonic()
        for i in active:
            st = self.slots[i]
            if st is None:
                continue  # cancelled mid-step
            self.positions[i] += 1
            nxt = self._sample(logits[i])
            st.generated.append(nxt)
            self.decode_tokens += 1
            if self.on_token is not None:
                self.on_token(st.rid, nxt, now)
            hit_cap = self.positions[i] + 1 >= self.serving.max_seq
            if (nxt == self.eos_id or len(st.generated) >= st.max_new
                    or hit_cap):
                self._finish_slot(i, now)
        self.steps += 1
        self.last_heartbeat = now
        return len(active)

    def run_until_drained(self, max_steps: int = 100_000) -> List[SeqState]:
        while (self.waiting or any(s is not None for s in self.slots)):
            if self.steps >= max_steps:
                break
            self.step()
        return self.finished

    # -- fault tolerance ----------------------------------------------------

    def heartbeat_ok(self) -> bool:
        dt = time.monotonic() - self.last_heartbeat
        self.healthy = dt <= self.serving.heartbeat_timeout_s or self.steps == 0
        return self.healthy

    @staticmethod
    def _copy_seq(s: Optional[SeqState]) -> Optional[SeqState]:
        # generated must be deep-copied: a shallow replace() would alias the
        # live list, letting post-snapshot tokens leak into the snapshot
        return (dataclasses.replace(s, generated=list(s.generated))
                if s else None)

    def snapshot(self) -> dict:
        out = {
            "cache": jax.tree.map(np.asarray, self.cache),
            "slots": [self._copy_seq(s) for s in self.slots],
            "positions": self.positions.copy(),
            "waiting": list(self.waiting),
            "steps": self.steps,
            "keys": np.asarray(self._keys),
            "slot_prompt": [None if p is None else p.copy()
                            for p in self._slot_prompt],
            "slot_fp": list(self._slot_extras_fp),
        }
        if self.pool is not None:
            out["paged"] = {
                "pt": None if self._pt is None else self._pt.copy(),
                "slot_pages": [list(p) for p in self._slot_pages],
                "high_water": self.pool.high_water,
            }
        return out

    def _put(self, x):
        """Host array(s) -> this engine's device (JAX's default when the
        engine is not pinned)."""
        return jax.device_put(x, self.device)

    def restore(self, snap: dict) -> None:
        self.cache = self._put(snap["cache"])
        self.slots = [self._copy_seq(s) for s in snap["slots"]]
        self.positions = snap["positions"].copy()
        self.waiting = list(snap["waiting"])
        self.steps = snap["steps"]
        if "keys" in snap:
            self._keys = self._put(snap["keys"])
        b = len(self.slots)
        self._slot_prompt = [None if p is None else p.copy()
                             for p in snap.get("slot_prompt",
                                               [None] * b)]
        self._slot_extras_fp = list(snap.get("slot_fp", [b""] * b))
        if self.pool is not None:
            # prefix-store entries hold page refs into the PRE-failure pool;
            # drain them first (decrefs fire against the old pool), then
            # derive a fresh allocator from the snapshot's ownership lists
            while self.prefix_store.evict_oldest() is not None:
                pass
            pv = snap.get("paged") or {}
            self._slot_pages = [list(p) for p in
                                pv.get("slot_pages",
                                       [[] for _ in self.slots])]
            old = self.pool
            self.pool = PagePool(self.serving.pool_pages,
                                 self.serving.kv_page_size,
                                 page_bytes=old.page_bytes)
            self.pool.reown([p for sp in self._slot_pages for p in sp])
            self.pool.high_water = max(self.pool.high_water,
                                       int(pv.get("high_water", 0)))
            if self.prefix_store.lru.on_evict is None:
                self.prefix_store.lru.on_evict = self._on_prefix_evict
            if self._pt is not None:
                self._pt = (np.zeros_like(self._pt) if pv.get("pt") is None
                            else np.asarray(pv["pt"], np.int32).copy())
        self.healthy = True
        self.last_heartbeat = time.monotonic()
