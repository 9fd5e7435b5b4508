"""Continuous-batching engine: slot-scheduled prefill + batched decode.

One engine tick = (admit arrived requests into free slots via bucketed
prefill) + (one batched ``decode_step`` over all slots). The batched cache
holds every slot's KV/SSM state with a **per-slot position vector**
(``cache["pos"]: (n_slots,) int32``), so slots sit at heterogeneous
context lengths inside a single jitted decode step — the paper's serial
accumulator with one accumulator per slot.

Two cache layouts (``docs/paged-kv.md``):

* **dense slots** (default): every slot statically reserves a
  ``max_len``-token KV region — simple, but over-provisioned exactly the
  way the paper warns against for any shared resource;
* **paged** (``paged=True``): KV lives in a shared pool of fixed-size
  physical pages mapped through per-slot block tables
  (:mod:`repro.serve.kv_pool`). Requests sharing a prompt prefix share
  physical pages (ref-counted, copy-on-write at the first divergent
  write), admission requires "free slot **and** enough free blocks"
  (preempt-free backpressure), and on the dense family a prefix-cache hit
  skips recomputing the shared prefill blocks entirely.

A :class:`~repro.serve.spec.Drafter` switches the decode tick to
**speculative** mode (``docs/spec-decode.md``): draft ``k`` tokens per
slot, score them in one ``(n_slots, k+1)`` ``verify_step``, commit each
slot's accepted prefix — up to ``k + 1`` tokens per tick, rejection being
a per-slot cursor rewind (plus a state-snapshot restore for recurrent
families).

**SLO-aware serving** (``docs/slo-scheduling.md``): with
``prefill_chunk_tokens`` set, long prompts prefill in fixed-budget
chunks interleaved with decode ticks, so an in-flight request's
inter-token latency is bounded by one chunk instead of one whole prompt.
With ``scheduling="slo"`` the scheduler admits by (priority, earliest
deadline) and the engine may *preempt* a running request whose deadline
is later than a waiting one's: its device state is spilled (dense slots:
a slot-row snapshot; paged: the block table is pinned and only the
per-slot state is snapshotted), the slot is handed over, and the victim
is revived later with bit-identical continuation. Both features preserve
greedy-token parity with the one-shot FIFO engine.

Shape discipline (everything ``jax.jit`` sees is from a fixed set):
  * decode: always ``(n_slots, 1)`` tokens against the same cache shapes;
  * speculative verify: always ``(n_slots, k + 1)`` tokens, one shape;
  * prefill: one shape per prompt bucket (attention families right-pad and
    pass ``prompt_len``; SSM/hybrid compile one prefill per exact length
    because pad tokens would pollute the recurrent state — see
    ``docs/serving.md``); suffix prefill adds one shape per
    (prefix blocks, suffix bucket) pair;
  * sampling: one ``(n_slots, vocab)`` mixed-policy call.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro import tracing
from repro.launch.costing import request_decode_cost, spec_request_decode_cost
from repro.layers.attention import resolve_attn_backend
from repro.parallel import (activate, replicate_uneven_kv_heads,
                            serve_cache_shardings, serve_rules_for)
from repro.serve.kv_pool import TRASH_BLOCK, BlockPool, blocks_needed
from repro.serve.metrics import (RequestMetrics, aggregate, paged_report,
                                 slo_report, spec_report)
from repro.serve.request import FinishReason, Request, RequestResult
from repro.serve.sampling import sample_batch
from repro.serve.scheduler import SlotScheduler
from repro.serve.spec import Drafter, verify_accept

__all__ = ["ServeEngine"]


# ---------------------------------------------------------------------------
# Compilation cache: engine callables are jitted once per
# (model config, cache layout, mesh) — constructing a second engine on the
# same model (dense + paged + spec benchmark sweeps) reuses the jitted
# functions and their XLA executables instead of recompiling everything.
# ---------------------------------------------------------------------------

_COMPILE_CACHE: Dict[tuple, Callable] = {}


def _cache_size() -> int:
    """Number of cached jitted callables (test probe: constructing a second
    engine with an identical layout must not grow this)."""
    return len(_COMPILE_CACHE)


def _clear_compile_cache() -> None:
    _COMPILE_CACHE.clear()


def _cached_jit(key: tuple, build: Callable[[], Callable]) -> Callable:
    fn = _COMPILE_CACHE.get(key)
    if fn is None:
        fn = _COMPILE_CACHE[key] = build()
    return fn


@dataclasses.dataclass
class _Inflight:
    """Host-side state of one admitted request (device state lives in the
    engine's batched cache at ``slot``)."""

    request: Request
    slot: int
    generated: List[int]
    next_token: int
    metrics: RequestMetrics
    #: spec mode: committed context length at each verify tick this
    #: request was active (feeds the acceptance-aware FLOPs pricing)
    tick_contexts: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Prefilling:
    """Host-side state of one request mid-chunked-prefill.

    The slot is scheduler-active but not yet in ``_inflight`` — no token
    has been emitted. Paged: the block table is planned up front but the
    slot's installed row stays all-trash (pos 0) until the final chunk,
    so interleaved decode ticks write only to the trash page. Dense
    attention: per-chunk suffix KV accumulates in ``kv_parts`` and the
    final chunk assembles + writes the whole slot row at once.
    """

    request: Request
    slot: int
    admitted_s: float
    done: int                 # prompt tokens already consumed
    chunks: int = 0
    #: recurrent families: carried cache-shaped state between chunks
    state: Optional[dict] = None
    #: attention families, dense slots: accumulated per-chunk suffix KV
    kv_parts: List = dataclasses.field(default_factory=list)
    plan: Optional[object] = None
    table: Optional["_SlotTable"] = None
    cached_tokens: int = 0


@dataclasses.dataclass
class _SlotTable:
    """Host mirror of one slot's block table (paged mode).

    ``shared`` marks logical blocks currently mapped to ref-shared pages
    (writes must not land there — admission redirects them to the trash
    page, and the reserved ``cow_spare`` absorbs the first divergent
    write).
    """

    blocks: List[int]
    shared: Set[int]
    cow_spare: Optional[int] = None
    tail_idx: Optional[int] = None


def _write_slot(cache: dict, pre: dict, slot):
    """Copy a batch=1 prefill cache into row ``slot`` of the batched cache.

    Every non-``pos`` leaf is laid out ``(stack, batch, ...)`` (layer or
    app-point stack first, batch axis second) in all model families;
    ``pos`` is the per-slot position vector and takes the prefill's scalar
    cursor. Jitted with the batched cache donated.
    """
    out = {}
    for key, big in cache.items():
        if key == "pos":
            out[key] = big.at[slot].set(pre["pos"].astype(big.dtype))
        else:
            out[key] = jax.tree.map(
                lambda b, s: b.at[:, slot].set(s[:, 0].astype(b.dtype)),
                big, pre[key])
    return out


def _read_slot(cache: dict, slot):
    """Exact inverse of :func:`_write_slot`: snapshot row ``slot`` of the
    batched cache as a batch=1 prefill-shaped tree (preemption spill).
    ``_write_slot(_read_slot(cache, s), s)`` round-trips bit-identically —
    both sides are pure gathers/scatters in the cache dtype."""
    out = {}
    for key, big in cache.items():
        if key == "pos":
            out[key] = big[slot]
        else:
            # gather-then-expand: plain slicing needs static bounds under
            # jit, and ``b[:, slot]`` gathers fine with a traced index
            out[key] = jax.tree.map(lambda b: b[:, slot][:, None], big)
    return out


def _read_paged_slot(cache, slot, *, has_ssm):
    """Snapshot a paged slot's per-slot dense state (cursor + recurrent
    state). The KV itself is NOT copied — the spilled request keeps its
    ref-counted pool pages pinned, so only the slot-indexed leaves move."""
    out = {"pos": cache["pos"][slot]}
    if has_ssm:
        out["ssm"] = jax.tree.map(lambda b: jnp.expand_dims(b[:, slot], 1),
                                  cache["ssm"])
    return out


def _restore_paged_slot(cache, snap, table_row, slot, *, has_ssm):
    """Revive a spilled paged request into ``slot``: reinstall its block
    table row and cursor, and restore any recurrent state."""
    out = dict(cache)
    out["block_tables"] = cache["block_tables"].at[slot].set(table_row)
    out["pos"] = cache["pos"].at[slot].set(
        snap["pos"].astype(cache["pos"].dtype))
    if has_ssm:
        out["ssm"] = jax.tree.map(
            lambda b, s: b.at[:, slot].set(s[:, 0].astype(b.dtype)),
            cache["ssm"], snap["ssm"])
    return out


# ---- paged device helpers (module-level so the compile cache can share
# them across engine instances; static layout via functools.partial) -------


def _gather_prefix(pool, ids, *, cdtype):
    """Cached prefix pages → dense ``(L, 1, P, Hk, D)`` K/V (compute
    dtype; dequantized if the pool is int8)."""
    from repro.layers.attention import dequantize_kv

    def flat(name):
        x = pool[name][:, ids]                   # (L, n, bs, ...)
        return x.reshape((x.shape[0], 1, -1) + x.shape[3:])

    k, v = flat("k"), flat("v")
    if "k_scale" in pool:
        k = dequantize_kv(k, flat("k_scale"), cdtype)
        v = dequantize_kv(v, flat("v_scale"), cdtype)
    return {"k": k, "v": v}


def _paged_write(cache, pre_kv, pre_state, write_ids, table_row, slot,
                 pre_pos, *, kv_key):
    """Scatter a prefill's K/V into the pool pages named by ``write_ids``
    (one per written logical block; shared/overhang blocks arrive
    redirected to the trash page), install the slot's block-table row +
    position, and write any per-slot dense state."""
    out = dict(cache)
    nb = write_ids.shape[0]

    def w(pool_leaf, s):
        s = s[:, 0]                              # (stack, S, ...)
        s = s.reshape((s.shape[0], nb, s.shape[1] // nb) + s.shape[2:])
        return pool_leaf.at[:, write_ids].set(s.astype(pool_leaf.dtype))

    out[kv_key] = jax.tree.map(w, cache[kv_key], pre_kv)
    if pre_state is not None:
        out["ssm"] = jax.tree.map(
            lambda b, s: b.at[:, slot].set(s[:, 0].astype(b.dtype)),
            cache["ssm"], pre_state)
    out["block_tables"] = cache["block_tables"].at[slot].set(table_row)
    out["pos"] = cache["pos"].at[slot].set(
        pre_pos.astype(cache["pos"].dtype))
    return out


def _cow_copy(cache, src, dst, slot, logical_idx, *, kv_key):
    """Copy-on-write: duplicate page ``src`` into the reserved spare
    ``dst`` and repoint this slot's table entry, so the imminent divergent
    write lands on a private page."""
    out = dict(cache)
    out[kv_key] = jax.tree.map(
        lambda p: p.at[:, dst].set(p[:, src]), cache[kv_key])
    out["block_tables"] = \
        cache["block_tables"].at[slot, logical_idx].set(dst)
    return out


def _clear_slot(cache, slot):
    """Point a freed slot's table at the trash page and rewind its cursor:
    its (masked-out) decode writes can then never corrupt pages
    reallocated to live requests."""
    out = dict(cache)
    out["block_tables"] = cache["block_tables"].at[slot].set(TRASH_BLOCK)
    out["pos"] = cache["pos"].at[slot].set(0)
    return out


class ServeEngine:
    """Continuous-batching server over a :class:`repro.models.api.Model`.

    Parameters
    ----------
    model, params:
        A built model and its parameters. Any decode-capable *text*
        family (dense / MoE / SSM / hybrid); VLM is rejected — the engine
        feeds token-only prompts.
    n_slots:
        Decode batch width — the number of requests in flight at once.
    max_len:
        Per-slot context capacity in tokens (prompt + generation).
    prompt_buckets:
        Prefill shape set (tokens); defaults to powers of two up to
        ``max_len``. Attention families right-pad prompts up to a bucket.
    paged:
        Use the paged KV pool instead of dense per-slot cache regions.
        Requires a KV-bearing family (dense / MoE / hybrid — pure SSM has
        nothing to page) and ``block_size`` dividing ``max_len`` (which
        makes the gathered paged view shape-identical to the dense cache,
        the key to bit-identical decode).
    block_size:
        Tokens per physical KV page (paged mode).
    n_blocks:
        Physical pages in the pool (paged mode). Defaults to the dense
        equivalent ``n_slots * max_len / block_size``; smaller values
        trade capacity for admission backpressure.
    rng:
        Key for sampled (non-greedy) requests. Defaults to ``PRNGKey(0)``.
    drafter:
        A :class:`repro.serve.spec.Drafter` switches the decode tick to
        *speculative* mode: each tick proposes ``drafter.k`` tokens per
        slot, scores them in one ``verify_step``, and commits the accepted
        prefix — up to ``k + 1`` tokens per tick instead of 1 (see
        ``docs/spec-decode.md``). Requires ``model.supports_spec_decode``.
        The scheduler then reserves a ``k``-row margin per request
        (tentative verify writes must stay inside the slot), and paged
        admission reserves the matching extra blocks.
    mesh:
        A ``jax.sharding.Mesh`` runs the engine sharded (see
        ``docs/sharded-serving.md``): parameters land tensor-parallel (heads / ff /
        experts on the ``model`` axis per ``rules``), the KV cache shards
        slots over ``data`` and KV heads over ``model``, and every jitted
        callable carries explicit NamedSharding in/out specs (donation
        preserved) so decode steps run without resharding transfers.
        Greedy decode is bit-identical to the single-device engine.
    rules:
        :class:`repro.parallel.ShardingRules` for the mesh; defaults to
        :func:`repro.parallel.serve_rules_for` of the model family (full
        TP/EP for attention families, data-parallel for recurrent ones —
        the bitwise-reproducible table).
    clock:
        Monotonic time source in seconds (injectable for deterministic
        tests — a :class:`repro.serve.clock.StepClock` turns the engine
        into an exact discrete-event simulator). Idle gaps before the
        next arrival are fast-forwarded, so a frozen clock still makes
        progress.
    prefill_chunk_tokens:
        Split prompts longer than this into fixed-budget prefill chunks,
        one chunk per engine tick, interleaved with decode ticks (None =
        one-shot prefill). Must be a multiple of the model's
        ``prefill_chunk_alignment`` (``cfg.ssd_chunk`` for recurrent
        families) and, paged, of ``block_size``; chunked prefill is
        greedy-token bit-identical to one-shot (``docs/slo-scheduling.md``
        — chunk-size guidance in
        :func:`repro.launch.costing.prefill_chunk_guidance`).
    scheduling:
        ``"fifo"`` (default, historical behaviour) or ``"slo"``: admit by
        (priority, earliest deadline) and preempt a running request when
        a waiting one has a strictly earlier deadline and no slot is
        free. Preemption spills the victim's state (dense: slot-row
        snapshot; paged: pinned block table + per-slot state) and revives
        it later bit-identically. Incompatible with a ``drafter`` (the
        verify window's tentative state cannot be spilled mid-flight).
    attn_backend:
        Override ``cfg.attn_backend`` for the paged decode/verify hot
        path: ``"jnp"`` streams the gathered dense KV view (reference),
        ``"pallas"`` runs the fused block-table flash kernels
        (``repro.kernels.paged_attention``), ``"auto"`` picks pallas on
        TPU and jnp elsewhere. ``None`` keeps the model config's value.
        Greedy decode tokens are identical across backends
        (``docs/kernels.md``).
    """

    def __init__(self, model, params, *, n_slots: int, max_len: int,
                 prompt_buckets: Sequence[int] = (), paged: bool = False,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 rng=None, drafter: Optional[Drafter] = None,
                 mesh=None, rules=None,
                 clock: Callable[[], float] = time.monotonic,
                 prefill_chunk_tokens: Optional[int] = None,
                 scheduling: str = "fifo",
                 attn_backend: Optional[str] = None):
        if attn_backend is not None:
            # override the config's paged-attention backend ("jnp" | "pallas"
            # | "auto"); baked into cfg so it keys the compile cache and the
            # jitted decode/verify closures see it as a static attribute
            model = dataclasses.replace(
                model, cfg=dataclasses.replace(model.cfg,
                                               attn_backend=attn_backend))
        if model.cfg.family == "encoder":
            raise ValueError("encoder-only arch has no decode step")
        if model.cfg.family == "vlm":
            raise ValueError("vlm serving is not supported: the engine "
                             "feeds token-only prompts, but vlm prefill "
                             "needs a patch batch")
        if drafter is not None and not model.supports_spec_decode:
            raise ValueError(
                f"family {model.cfg.family!r} (cfg {model.cfg.name!r}) has "
                "no exact multi-token verify — speculative decoding needs "
                "Model.supports_spec_decode")
        if scheduling not in SlotScheduler.POLICIES:
            raise ValueError(f"unknown scheduling {scheduling!r}; expected "
                             f"one of {SlotScheduler.POLICIES}")
        if scheduling == "slo" and drafter is not None:
            raise ValueError(
                "scheduling='slo' is incompatible with speculative "
                "decoding: preemption would have to spill the drafter's "
                "per-slot state and the verify window's tentative writes")
        self._chunk = prefill_chunk_tokens
        if self._chunk is not None:
            if self._chunk < 1:
                raise ValueError("prefill_chunk_tokens must be >= 1")
            if not model.supports_chunked_prefill:
                raise ValueError(
                    f"family {model.cfg.family!r} (cfg {model.cfg.name!r}) "
                    "does not support chunked prefill "
                    "(Model.supports_chunked_prefill)")
            align = model.prefill_chunk_alignment
            if self._chunk % align:
                raise ValueError(
                    f"prefill_chunk_tokens {self._chunk} must be a multiple "
                    f"of the model's chunk alignment {align} (ssd_chunk for "
                    "recurrent families — misaligned chunks change the SSD "
                    "scan's block boundaries and break bit-exactness)")
            if paged and self._chunk % block_size:
                raise ValueError(
                    f"prefill_chunk_tokens {self._chunk} must be a multiple "
                    f"of block_size {block_size} so every chunk's KV lands "
                    "on whole pool pages")
            if getattr(model.cfg, "kv_cache_dtype", None) == "int8":
                raise ValueError(
                    "chunked prefill does not support int8 KV caches: "
                    "per-chunk suffix KV is quantized per chunk, which "
                    "breaks bit-exactness with the one-shot prefill scales")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.drafter = drafter
        self.spec_k = drafter.k if drafter is not None else 0
        self.scheduling = scheduling
        self.scheduler = SlotScheduler(n_slots, max_len,
                                       [b for b in prompt_buckets
                                        if b <= max_len],
                                       spec_margin=self.spec_k,
                                       policy=scheduling, clock=clock)
        self._clock = clock
        self._rng = jax.random.PRNGKey(0) if rng is None else rng
        self._padded = model.supports_padded_prefill
        self.paged = paged

        self.mesh = mesh
        self.rules = None
        self._param_sh = self._cache_sh = self._rep = None
        if mesh is not None:
            self.rules = rules if rules is not None \
                else serve_rules_for(model.cfg.family)
            self.rules = replicate_uneven_kv_heads(
                self.rules, model.cfg.n_kv_heads, mesh)
            self._rep = NamedSharding(mesh, PartitionSpec())
            from repro.launch.steps import build_shardings, infer_param_axes
            self._param_sh = build_shardings(
                params, infer_param_axes(params), mesh, self.rules)
            params = jax.device_put(params, self._param_sh)
        self.params = params
        #: everything a cached jitted callable closes over: the config
        #: (family dispatch, dtypes, strategies), the cache layout flavor,
        #: and the mesh/rules the sharding specs are built from. Mesh
        #: engines additionally key on the layout shapes: the baked
        #: in/out sharding trees depend on them (an indivisible slot or
        #: head dim replicates), so two mesh engines may only share a jit
        #: when their cache shapes agree.
        layout_key = (n_slots, max_len, block_size, n_blocks) \
            if mesh is not None else ()
        self._jit_key = (model.cfg, paged, mesh, self.rules) + layout_key

        if paged:
            self._init_paged(block_size, n_blocks)
        else:
            cache = model.init_cache(n_slots, max_len)
            cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
            self.cache = self._place_cache(cache)
            self._decode = self._build(
                "decode", model.decode_step, donate=(1,),
                in_specs=(self._param_sh, self._cache_sh, self._rep),
                out_specs=(self._rep, self._cache_sh))
            self._write = self._build(
                "write", _write_slot, donate=(0,),
                in_specs=(self._cache_sh, self._rep, self._rep),
                out_specs=self._cache_sh)
            self._read = self._build(
                "read_slot", _read_slot,
                in_specs=(self._cache_sh, self._rep),
                out_specs=self._rep)

        if self._padded:
            self._prefill = self._build(
                "prefill",
                lambda p, b, pl: model.prefill(p, b, max_len=max_len,
                                               prompt_len=pl),
                in_specs=(self._param_sh, self._rep, self._rep),
                out_specs=self._rep, key_extra=(max_len,))
        else:
            self._prefill = self._build(
                "prefill",
                lambda p, b: model.prefill(p, b, max_len=max_len),
                in_specs=(self._param_sh, self._rep),
                out_specs=self._rep, key_extra=(max_len,))
        if self._chunk is not None:
            fam = model.cfg.family
            self._chunk_kv_key = "kv" if fam == "hybrid" else "layers"
            if fam == "ssm":
                self._prefill_chunk = self._build(
                    "prefill_chunk",
                    lambda p, b, st: model.prefill_chunk(p, b, state=st),
                    in_specs=(self._param_sh, self._rep, self._rep),
                    out_specs=self._rep)
            elif fam == "hybrid":
                self._prefill_chunk = self._build(
                    "prefill_chunk",
                    lambda p, b, st, pre: model.prefill_chunk(
                        p, b, state=st, prefix_kv=pre),
                    in_specs=(self._param_sh, self._rep, self._rep,
                              self._rep),
                    out_specs=self._rep)
            elif not hasattr(self, "_suffix_prefill"):
                # attention families chunk via suffix prefill (chunk 0 uses
                # a zero-length prefix); the paged dense engine already
                # built this callable for prefix-cache hits
                self._suffix_prefill = self._build(
                    "suffix_prefill",
                    lambda p, b, pre, pl: model.prefill_suffix(
                        p, b, prefix=pre, prompt_len=pl),
                    in_specs=(self._param_sh, self._rep, self._rep,
                              self._rep),
                    out_specs=self._rep)
        self._sample = self._build("sample", sample_batch)
        if drafter is not None:
            if not paged:
                self._verify = self._build(
                    "verify", model.verify_step, donate=(1,),
                    in_specs=(self._param_sh, self._cache_sh, self._rep),
                    out_specs=(self._rep, self._cache_sh, self._rep))
            # paged: verify is built lazily per live-block bucket
            # (_verify_for), mirroring the decode path
            self._commit = self._build(
                "commit", model.commit_verified, donate=(0,),
                in_specs=(self._cache_sh, self._rep, self._rep),
                out_specs=self._cache_sh)
            self._accept = self._build("accept", verify_accept)

        self._inflight: Dict[int, _Inflight] = {}
        #: slot -> mid-chunked-prefill request state
        self._prefilling: Dict[int, _Prefilling] = {}
        #: uid -> spilled (preempted) request record awaiting revival
        self._spilled: Dict[int, dict] = {}
        self._preemptions = 0
        self._spills = 0
        self._revivals = 0
        self._chunk_ticks = 0
        self._steps = 0
        self._occupancy_sum = 0.0
        self._fast_forward_s = 0.0
        # run() resets the clock origin; set here so preempt() works before
        # the first run (tests drive the lifecycle methods directly)
        self._t_start = self._clock()
        self._compile_s = 0.0
        self._on_logits = None
        self._log_start = 0
        self._spec_ticks = 0
        self._spec_emitted = 0
        self._spec_slot_steps = 0.0
        self._accept_hist = [0] * (self.spec_k + 1)
        self._draft_steps_start = 0
        self._tick_contexts: Dict[int, List[int]] = {}
        if drafter is not None:
            drafter.bind(self)

    # ---- paged setup -------------------------------------------------------
    def _init_paged(self, block_size: int, n_blocks: Optional[int]) -> None:
        model = self.model
        spec = model.cache_spec()
        if not spec.pageable:
            raise ValueError(
                f"family {model.cfg.family!r} has no KV cache to page — "
                "its decode state is constant-size per slot")
        if self.max_len % block_size:
            raise ValueError(
                f"block_size {block_size} must divide max_len "
                f"{self.max_len} so the gathered paged view matches the "
                "dense cache shape exactly")
        self.block_size = block_size
        self._max_blocks = self.max_len // block_size
        self.n_blocks = n_blocks if n_blocks is not None \
            else self.n_slots * self._max_blocks
        self._pool = BlockPool(self.n_blocks, block_size)
        self._tables: Dict[int, _SlotTable] = {}
        # dense family: prefix hits skip prefill compute via suffix prefill;
        # partial-tail sharing is pointless there (the tail is recomputed),
        # so tail matching — and with it CoW — is the full-prefill
        # families' (MoE / hybrid) regime
        self._suffix_capable = model.cfg.family == "dense"
        self._match_tail = not self._suffix_capable
        # prefix-content reuse is exact only when a prompt position's KV is
        # independent of the rest of the prefill batch: dense and hybrid
        # (causal) always, MoE only dropless — below that, expert capacity
        # couples a token's output to the total prefill length, so two
        # requests' "identical" prefixes can hold different KV. Capacity-
        # limited MoE still pages memory but never shares content (its
        # prompt blocks stay out of the trie).
        self._prefix_share = model.cfg.family != "moe" \
            or model.supports_padded_prefill
        if not self._prefix_share:
            self._match_tail = False
        self._spec = spec
        # physical pages: pool blocks 1..n plus the id-0 trash page
        self.cache = self._place_cache(model.init_paged_cache(
            self.n_slots, self.n_blocks + 1, block_size, self._max_blocks))
        self._kv_key = kv_key = \
            "kv" if model.cfg.family == "hybrid" else "layers"
        kv_sh = self._cache_sh[kv_key] if self._cache_sh is not None else None
        # decode/verify are built lazily per live-block bucket (_decode_for /
        # _verify_for): attention gathers only up to the in-flight high-water
        # block instead of the full table width, so a mostly-shallow batch
        # streams a fraction of the padded KV (docs/kernels.md)
        if self._suffix_capable:
            self._suffix_prefill = self._build(
                "suffix_prefill",
                lambda p, b, pre, pl: model.prefill_suffix(
                    p, b, prefix=pre, prompt_len=pl),
                in_specs=(self._param_sh, self._rep, self._rep, self._rep),
                out_specs=self._rep)
        self._gather_prefix = self._build(
            "gather_prefix",
            functools.partial(_gather_prefix, cdtype=model.cfg.cdtype),
            in_specs=(kv_sh, self._rep), out_specs=self._rep)
        self._paged_write = self._build(
            "paged_write", functools.partial(_paged_write, kv_key=kv_key),
            donate=(0,),
            in_specs=(self._cache_sh,) + (self._rep,) * 6,
            out_specs=self._cache_sh)
        self._cow_copy = self._build(
            "cow_copy", functools.partial(_cow_copy, kv_key=kv_key),
            donate=(0,),
            in_specs=(self._cache_sh,) + (self._rep,) * 4,
            out_specs=self._cache_sh)
        self._clear_slot = self._build(
            "clear_slot", _clear_slot, donate=(0,),
            in_specs=(self._cache_sh, self._rep),
            out_specs=self._cache_sh)
        has_ssm = model.cfg.family == "hybrid"
        self._read_paged = self._build(
            "read_paged_slot",
            functools.partial(_read_paged_slot, has_ssm=has_ssm),
            in_specs=(self._cache_sh, self._rep), out_specs=self._rep)
        self._restore_paged = self._build(
            "restore_paged_slot",
            functools.partial(_restore_paged_slot, has_ssm=has_ssm),
            donate=(0,),
            in_specs=(self._cache_sh,) + (self._rep,) * 3,
            out_specs=self._cache_sh)
        self._prefix_hits = 0
        self._shared_block_hits = 0
        self._cow_count = 0
        self._admissions = 0
        self._block_occ_sum = 0.0
        self._peak_blocks = 0
        # attention KV traffic accounting (both numbers priced per tick from
        # the same cursors, independent of which backend actually ran):
        # gathered = what the jnp gather path streams (n_slots × high-water
        # bucket), fused = what the block-table kernel touches (live blocks
        # only). _kv_step_log keeps the per-tick (gathered, fused) pairs for
        # depth-resolved reporting (benchmarks/serving.py --backends).
        self._gathered_kv_bytes = 0
        self._fused_kv_bytes = 0
        self._kv_step_log: List[Tuple[int, int]] = []

    # ---- sharding + compile-cache plumbing ---------------------------------
    def _place_cache(self, cache):
        """Compute (and remember) the cache sharding tree and place the
        cache accordingly; identity on a mesh-less engine."""
        if self.mesh is None:
            return cache
        self._cache_sh = serve_cache_shardings(cache, self.mesh, self.rules,
                                               paged=self.paged)
        return jax.device_put(cache, self._cache_sh)

    def _ctx(self, fn):
        """Run ``fn`` inside this engine's sharding context (so
        ``constrain`` annotations bind at trace time); identity without a
        mesh."""
        if self.mesh is None:
            return fn
        mesh, rules = self.mesh, self.rules

        @functools.wraps(fn)
        def wrapped(*args):
            with activate(mesh, rules):
                return fn(*args)

        return wrapped

    def _build(self, name: str, fn, *, donate: Tuple[int, ...] = (),
               in_specs=None, out_specs=None, key_extra: tuple = ()):
        """Jit ``fn`` through the module compile cache, as the executable
        ``jit_serve_<name>`` (:func:`repro.tracing.executable`).

        The key is ``(cfg, paged, mesh, rules, name, *key_extra)`` — two
        engines with the same model and cache layout share one jitted
        callable (and its per-shape executables). On a mesh the callable
        carries explicit NamedSharding in/out specs so no input or output
        ever reshards at the jit boundary (donation preserved).
        """
        key = self._jit_key + (name,) + tuple(key_extra)

        def builder():
            kwargs = {}
            if donate:
                kwargs["donate_argnums"] = donate
            if self.mesh is not None:
                if in_specs is not None:
                    kwargs["in_shardings"] = in_specs
                if out_specs is not None:
                    kwargs["out_shardings"] = out_specs
            return jax.jit(tracing.executable(name, fn), **kwargs)

        return self._ctx(_cached_jit(key, builder))

    # ---- live-block bucketing (paged) --------------------------------------
    def _hw_buckets(self) -> List[int]:
        """The block-count buckets decode/verify compile against: powers of
        two up to the table width, plus the width itself."""
        buckets = []
        b = 1
        while b < self._max_blocks:
            buckets.append(b)
            b <<= 1
        buckets.append(self._max_blocks)
        return buckets

    def _live_blocks(self, window: int) -> int:
        """Bucketed high-water block count covering every in-flight slot's
        cursor plus ``window`` rows written this tick (1 for decode, k+1
        for a verify pass). Computed host-side from the same cursors the
        device cache holds, then rounded up to the next power of two so the
        number of compiled decode/verify shapes stays logarithmic in the
        table width."""
        need = 1
        for inf in self._inflight.values():
            top = inf.metrics.prompt_tokens + len(inf.generated) + window - 1
            need = max(need, top // self.block_size + 1)
        b = 1
        while b < need:
            b <<= 1
        return min(b, self._max_blocks)

    def _decode_for(self, hw: int):
        """Paged decode callable that reads only the first ``hw`` block-table
        columns (cached per bucket; attention output for every live slot is
        bit-identical to the full-width gather — trailing columns are fully
        masked, contributing exact zeros to the softmax)."""
        model = self.model
        return self._build(
            "decode",
            lambda p, c, t, _hw=hw: model.paged_decode_step(
                p, c, t, live_blocks=_hw),
            donate=(1,),
            in_specs=(self._param_sh, self._cache_sh, self._rep),
            out_specs=(self._rep, self._cache_sh),
            key_extra=(hw,))

    def _verify_for(self, hw: int):
        """Paged verify callable bounded to ``hw`` block-table columns; the
        bucket must cover the cursor plus the tentative k+1-row window."""
        model = self.model
        return self._build(
            "verify",
            lambda p, c, t, _hw=hw: model.paged_verify_step(
                p, c, t, live_blocks=_hw),
            donate=(1,),
            in_specs=(self._param_sh, self._cache_sh, self._rep),
            out_specs=(self._rep, self._cache_sh, self._rep),
            key_extra=(hw,))

    def _kv_bytes_tick(self, hw: int, window: int) -> Tuple[int, int]:
        """(gathered, fused) attention KV bytes for one tick at bucket
        ``hw``: the jnp gather path materializes ``n_slots × hw`` blocks
        whether live or not; the fused kernel touches only each slot's live
        blocks (dead pages are index-redirected and elided)."""
        blk = self._spec.kv_block_bytes(self.block_size)
        gathered = self.n_slots * hw * blk
        fused = 0
        for inf in self._inflight.values():
            top = inf.metrics.prompt_tokens + len(inf.generated) + window - 1
            fused += (top // self.block_size + 1) * blk
        return gathered, fused

    # ---- time --------------------------------------------------------------
    def _now(self, t_start: float) -> float:
        """Engine clock in seconds: wall time plus fast-forwarded idle."""
        return (self._clock() - t_start) + self._fast_forward_s

    # ---- lifecycle ---------------------------------------------------------
    def _next_key(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _block_gate(self, req: Request) -> bool:
        """Invariant 6: admission needs enough free pool blocks for the
        request's worst-case lifetime (prefix hits count as free; spec
        mode adds the verify window's tentative-write margin)."""
        return self._pool.can_admit(req.prompt,
                                    req.max_new_tokens + self.spec_k,
                                    match_tail=self._match_tail)

    def _plan_tables(self, req: Request):
        """Reserve pool pages for one admission: share matched prefix
        pages, allocate the rest (plus the CoW spare for a matched tail),
        and build the slot's logical→physical table. In spec mode the
        plan covers ``spec_k`` rows past the worst-case length, so every
        tentative verify write lands on a slot-private page."""
        pool, bs = self._pool, self.block_size
        plan = pool.plan(req.prompt, req.max_new_tokens + self.spec_k,
                         match_tail=self._match_tail)
        # share before alloc: a matched evictable page must be revived
        # before allocation can consider evicting it
        for b in plan.full_matched:
            pool.share(b)
        if plan.tail_matched is not None:
            pool.share(plan.tail_matched)
        fresh = iter(pool.alloc(plan.new_needed))
        n_full = len(plan.full_matched)
        table = _SlotTable(blocks=list(plan.full_matched),
                           shared=set(range(n_full)))
        if plan.tail_matched is not None:
            table.tail_idx = n_full              # == prompt_len // bs
        for i in range(n_full, plan.n_logical):
            if i == table.tail_idx:
                table.blocks.append(plan.tail_matched)
                table.shared.add(i)
            else:
                table.blocks.append(next(fresh))
        if plan.tail_matched is not None:
            table.cow_spare = next(fresh)
        return plan, table

    def _register_prompt_blocks(self, req: Request, plan,
                                table: _SlotTable) -> None:
        """Publish this admission's privately-written prompt pages in the
        prefix trie (matched pages are already registered)."""
        if not self._prefix_share:
            return
        bs, p = self.block_size, req.prompt_len
        for i in range(len(plan.full_matched), p // bs):
            self._pool.register(table.blocks[i], req.prompt[: (i + 1) * bs])
        if self._match_tail and p % bs and plan.tail_matched is None:
            self._pool.register(table.blocks[p // bs], req.prompt)

    def _paged_prefill(self, slot: int, req: Request):
        """Prefill under the paged cache; returns the first-token logits.

        Dense family with a prefix hit: gather the cached prefix pages and
        run the *suffix-only* prefill — the O(prefix) projection/attention
        work is skipped, which is where the TTFT win on shared-prefix
        workloads comes from. Everything else: full (bucketed or
        exact-length) prefill; shared logical blocks write to the trash
        page so cached content is never clobbered.
        """
        pool, bs, p = self._pool, self.block_size, req.prompt_len
        plan, table = self._plan_tables(req)
        self._admissions += 1
        if plan.n_shared:
            self._prefix_hits += 1
            self._shared_block_hits += plan.n_shared
        prompt = req.prompt_array()
        # dense suffix path: recompute at least one position so the
        # last-token logits exist even when every prompt block matched
        n_pref = min(len(plan.full_matched), (p - 1) // bs) \
            if self._suffix_capable else 0
        if n_pref > 0:
            prefix = self._gather_prefix(
                self.cache[self._kv_key],
                jnp.asarray(table.blocks[:n_pref], jnp.int32))
            suffix = prompt[0, n_pref * bs:]
            pad = -len(suffix) % bs
            toks = np.zeros((1, len(suffix) + pad), np.int32)
            toks[0, : len(suffix)] = suffix
            logits, pre = self._suffix_prefill(
                self.params, {"tokens": toks}, prefix,
                jnp.asarray(p, jnp.int32))
            first_logical = n_pref
        else:
            if self._padded:
                bucket = self.scheduler.bucket_for(p)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :p] = prompt[0]
                logits, pre = self._prefill(self.params, {"tokens": toks},
                                            jnp.asarray(p, jnp.int32))
            else:
                logits, pre = self._prefill(self.params, {"tokens": prompt})
            first_logical = 0
        kv, state = self.model.split_prefill_cache(pre)
        n_written = kv["k"].shape[2] // bs
        write_ids = []
        for i in range(first_logical, first_logical + n_written):
            if i >= len(table.blocks) or i in table.shared:
                write_ids.append(TRASH_BLOCK)
            else:
                write_ids.append(table.blocks[i])
        row = np.full((self._max_blocks,), TRASH_BLOCK, np.int32)
        row[: len(table.blocks)] = table.blocks
        self.cache = self._paged_write(
            self.cache, kv, state, jnp.asarray(write_ids, jnp.int32),
            jnp.asarray(row), slot, pre["pos"])
        self._register_prompt_blocks(req, plan, table)
        self._tables[slot] = table
        return logits, n_pref * bs

    def _apply_cow(self, slot: int) -> None:
        """First divergent write is imminent (the request enters the decode
        loop): copy the shared tail page into the reserved spare."""
        table = self._tables[slot]
        if table.cow_spare is None:
            return
        src, dst = table.blocks[table.tail_idx], table.cow_spare
        self.cache = self._cow_copy(self.cache, src, dst, slot,
                                    table.tail_idx)
        self._pool.free(src)
        table.blocks[table.tail_idx] = dst
        table.shared.discard(table.tail_idx)
        table.cow_spare = None
        self._cow_count += 1

    def _release_paged(self, slot: int) -> None:
        table = self._tables.pop(slot)
        for b in table.blocks:
            self._pool.free(b)
        if table.cow_spare is not None:
            self._pool.free(table.cow_spare)
        self.cache = self._clear_slot(self.cache, slot)

    def _admission_gate(self, req: Request) -> bool:
        """Paged admission gate: a spilled request already holds its
        worst-case block reservation (revival allocates nothing), fresh
        requests must fit the pool (invariant 6)."""
        return req.uid in self._spilled or self._block_gate(req)

    def _admit(self, slot: int, req: Request, now_s: float,
               results: List[RequestResult]) -> None:
        """Bind ``req`` to ``slot``: revive it if it was spilled by a
        preemption, start a chunked prefill if its prompt exceeds the
        chunk budget, else prefill in one shot and seed its first token."""
        with TraceAnnotation(tracing.ADMIT, uid=req.uid,
                             prompt_len=req.prompt_len):
            if req.uid in self._spilled:
                self._revive(slot, req)
                return
            if self._chunk is not None and req.prompt_len > self._chunk:
                self._begin_chunked(slot, req, now_s)
                return
            p = req.prompt_len
            cached_tokens = 0
            if self.paged:
                logits, cached_tokens = self._paged_prefill(slot, req)
            else:
                prompt = req.prompt_array()
                if self._padded:
                    bucket = self.scheduler.bucket_for(p)
                    toks = np.zeros((1, bucket), np.int32)
                    toks[0, :p] = prompt[0]
                    logits, pre = self._prefill(self.params, {"tokens": toks},
                                                jnp.asarray(p, jnp.int32))
                else:
                    logits, pre = self._prefill(self.params,
                                                {"tokens": prompt})
                self.cache = self._write(self.cache, pre, slot)
            if self.drafter is not None:
                self.drafter.admit(slot, req.prompt)
            self._seed(slot, req, logits, now_s, cached_tokens, 1, results)

    def _seed(self, slot: int, req: Request, logits, admitted_s: float,
              cached_tokens: int, chunks: int,
              results: List[RequestResult]) -> None:
        """Sample the first token from prefill logits and move the request
        into the decode set (or finish it on the spot)."""
        sampled = req.sampler(
            logits[:, -1], None if req.sampler.greedy else self._next_key())
        with TraceAnnotation(tracing.PULL):
            first = int(np.asarray(sampled)[0])
        t_first = self._now(self._t_start)
        metrics = RequestMetrics(arrival_s=req.arrival_s,
                                 admitted_s=admitted_s,
                                 first_token_s=t_first,
                                 prompt_tokens=req.prompt_len,
                                 cached_prompt_tokens=cached_tokens,
                                 deadline_s=req.deadline_s,
                                 prefill_chunks=chunks)
        inf = _Inflight(request=req, slot=slot, generated=[first],
                        next_token=first, metrics=metrics)
        if first == req.eos_id or req.max_new_tokens == 1:
            self._finish(inf, t_first, results)
        else:
            if self.paged:
                self._apply_cow(slot)
            self._inflight[slot] = inf

    # ---- chunked prefill ---------------------------------------------------
    def _begin_chunked(self, slot: int, req: Request, now_s: float) -> None:
        """Open a chunked prefill: reserve paged blocks up front (the slot's
        installed table row stays all-trash until the final chunk) and seed
        the recurrent families' carried state."""
        pf = _Prefilling(request=req, slot=slot, admitted_s=now_s, done=0)
        if self.paged:
            plan, table = self._plan_tables(req)
            self._admissions += 1
            if plan.n_shared:
                self._prefix_hits += 1
                self._shared_block_hits += plan.n_shared
            pf.plan, pf.table = plan, table
            if self._suffix_capable:
                # prefix-cache hit: skip the matched blocks' compute and
                # start the chunk cursor past them (same bound as the
                # one-shot suffix path: at least one position recomputed)
                n_pref = min(len(plan.full_matched),
                             (req.prompt_len - 1) // self.block_size)
                pf.done = pf.cached_tokens = n_pref * self.block_size
        fam = self.model.cfg.family
        if fam in ("ssm", "hybrid"):
            cache1 = self.model.init_cache(1, self.max_len)
            state_key = "layers" if fam == "ssm" else "ssm"
            pf.state = {state_key: cache1[state_key],
                        "pos": jnp.zeros((), jnp.int32)}
        self._prefilling[slot] = pf

    def _empty_prefix(self):
        """Zero-length prefix K/V tree — chunk 0 of an attention or hybrid
        chunked prefill is a suffix prefill with nothing in front."""
        key = self._kv_key if self.paged else self._chunk_kv_key
        kv = self.cache[key]
        cd = self.model.cfg.cdtype
        return {name: jnp.zeros(
            (kv[name].shape[0], 1, 0) + kv[name].shape[3:], cd)
            for name in ("k", "v")}

    def _chunk_prefix_kv(self, pf: _Prefilling):
        """Dense K/V over the first ``pf.done`` prompt tokens, feeding the
        next chunk's suffix prefill (paged: gathered back from the pool
        pages this prefill already wrote; dense slots: the accumulated
        device-side parts, merged lazily)."""
        if pf.done == 0:
            return self._empty_prefix()
        if self.paged:
            ids = pf.table.blocks[: pf.done // self.block_size]
            return self._gather_prefix(self.cache[self._kv_key],
                                       jnp.asarray(ids, jnp.int32))
        if len(pf.kv_parts) > 1:
            pf.kv_parts = [jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=2), *pf.kv_parts)]
        return pf.kv_parts[0]

    def _store_chunk_kv(self, pf: _Prefilling, kv, final: bool, state_final,
                        slot: int) -> None:
        """Bank one chunk's suffix K/V. Paged: scatter onto this chunk's
        pool pages now (shared/overhang logical blocks divert to the trash
        page; rows are zero-padded up to whole pages) and install the real
        table row + cursor + recurrent state only with the final chunk.
        Dense slots: accumulate on device, then write the whole slot row
        once. Rows past the prompt are garbage either way — masked by
        ``pos`` until decode overwrites them."""
        p = pf.request.prompt_len
        if self.paged:
            bs = self.block_size
            pad_rows = -kv["k"].shape[2] % bs
            if pad_rows:
                kv = jax.tree.map(
                    lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, pad_rows)]
                                      + [(0, 0)] * (x.ndim - 3)), kv)
            n_written = kv["k"].shape[2] // bs
            first_logical = pf.done // bs
            table = pf.table
            write_ids = []
            for i in range(first_logical, first_logical + n_written):
                if i >= len(table.blocks) or i in table.shared:
                    write_ids.append(TRASH_BLOCK)
                else:
                    write_ids.append(table.blocks[i])
            row = np.full((self._max_blocks,), TRASH_BLOCK, np.int32)
            if final:
                row[: len(table.blocks)] = table.blocks
            pos = jnp.asarray(p if final else 0, jnp.int32)
            self.cache = self._paged_write(
                self.cache, kv, state_final,
                jnp.asarray(write_ids, jnp.int32), jnp.asarray(row),
                slot, pos)
        else:
            pf.kv_parts.append(kv)
            if final:
                merged = self._chunk_prefix_kv(pf)
                pad_rows = self.max_len - merged["k"].shape[2]
                if pad_rows:
                    merged = jax.tree.map(
                        lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, pad_rows)]
                                          + [(0, 0)] * (x.ndim - 3)), merged)
                pre = {self._chunk_kv_key: merged,
                       "pos": jnp.asarray(p, jnp.int32)}
                if state_final is not None:
                    pre["ssm"] = state_final
                self.cache = self._write(self.cache, pre, slot)

    def _prefill_tick(self, results: List[RequestResult]) -> None:
        """Advance the lowest-numbered prefilling slot by one chunk; the
        final chunk installs the slot's cache state and seeds the first
        token exactly like a one-shot admission."""
        slot = min(self._prefilling)
        pf = self._prefilling[slot]
        req = pf.request
        p = req.prompt_len
        take = min(self._chunk, p - pf.done)
        end = pf.done + take
        final = end >= p
        pf.chunks += 1
        self._chunk_ticks += 1
        fam = self.model.cfg.family
        if fam == "ssm":
            toks = req.prompt_array()[:, pf.done:end]
            logits, pf.state = self._prefill_chunk(
                self.params, {"tokens": toks}, pf.state)
            if final:
                # the carried state IS the prefill cache
                self.cache = self._write(self.cache, pf.state, slot)
        elif fam == "hybrid":
            toks = req.prompt_array()[:, pf.done:end]
            prefix = self._chunk_prefix_kv(pf)
            logits, out = self._prefill_chunk(
                self.params, {"tokens": toks}, pf.state, prefix)
            pf.state = {"ssm": out["ssm"], "pos": out["pos"]}
            self._store_chunk_kv(pf, out["kv"], final,
                                 out["ssm"] if final else None, slot)
        else:
            prefix = self._chunk_prefix_kv(pf)
            toks = np.zeros((1, take), np.int32)
            toks[0, :] = req.prompt[pf.done:end]
            logits, pre = self._suffix_prefill(
                self.params, {"tokens": toks}, prefix,
                jnp.asarray(end, jnp.int32))
            self._store_chunk_kv(pf, pre["layers"], final, None, slot)
        pf.done = end
        if final:
            self._prefilling.pop(slot)
            if self.paged:
                self._register_prompt_blocks(req, pf.plan, pf.table)
                self._tables[slot] = pf.table
            if self.drafter is not None:
                self.drafter.admit(slot, req.prompt)
            self._seed(slot, req, logits, pf.admitted_s, pf.cached_tokens,
                       pf.chunks, results)

    # ---- preemption --------------------------------------------------------
    def preempt(self, slot: int) -> None:
        """Spill the request in ``slot`` and return it to the ready queue.

        A decoding request's device state is snapshotted (dense slots: the
        whole slot row; paged: only the per-slot cursor/recurrent state —
        its pool pages stay pinned under their refcounts, which also makes
        them immune to eviction storms) and revived bit-identically at its
        next admission. A mid-prefill request is cheaper: progress is
        discarded, its pages are freed, and it restarts from scratch — no
        token was emitted yet, so nothing observable is lost.
        """
        now = self._now(self._t_start)
        if slot in self._inflight:
            inf = self._inflight.pop(slot)
            inf.metrics.preempted += 1
            rec = {"request": inf.request, "generated": inf.generated,
                   "next_token": inf.next_token, "metrics": inf.metrics}
            if self.paged:
                rec["snap"] = self._read_paged(self.cache, slot)
                rec["table"] = self._tables.pop(slot)
                self.cache = self._clear_slot(self.cache, slot)
            else:
                rec["snap"] = self._read(self.cache, slot)
            self._spilled[inf.request.uid] = rec
            self._spills += 1
        elif slot in self._prefilling:
            pf = self._prefilling.pop(slot)
            if self.paged:
                for b in pf.table.blocks:
                    self._pool.free(b)
                if pf.table.cow_spare is not None:
                    self._pool.free(pf.table.cow_spare)
                self.cache = self._clear_slot(self.cache, slot)
        else:
            raise KeyError(f"slot {slot} has no preemptible request")
        self.scheduler.preempt(slot, now)
        self._preemptions += 1

    def _revive(self, slot: int, req: Request) -> None:
        """Reinstall a spilled request into ``slot`` and resume decoding
        exactly where it left off (its TTFT was banked at first
        admission; only queueing-for-revival time is added)."""
        rec = self._spilled.pop(req.uid)
        if self.paged:
            table = rec["table"]
            row = np.full((self._max_blocks,), TRASH_BLOCK, np.int32)
            row[: len(table.blocks)] = table.blocks
            self.cache = self._restore_paged(self.cache, rec["snap"],
                                             jnp.asarray(row), slot)
            self._tables[slot] = table
        else:
            self.cache = self._write(self.cache, rec["snap"], slot)
        self._inflight[slot] = _Inflight(
            request=req, slot=slot, generated=rec["generated"],
            next_token=rec["next_token"], metrics=rec["metrics"])
        self._revivals += 1

    def _maybe_preempt(self, now_s: float) -> None:
        """SLO policy: when no slot is free and the best waiting request
        strictly outranks the worst running one, preempt the latter — at
        most one preemption per tick; the strict-rank requirement plus
        uid tiebreak means a preempted pair can never thrash."""
        if self.scheduler.has_free or not self._inflight:
            return
        cand = self.scheduler.ready_head(now_s)
        if cand is None:
            return
        if self.paged and not self._admission_gate(cand):
            return   # freeing a slot would not make the candidate fit

        def rank(r):
            return (-r.priority,
                    r.deadline_s if r.deadline_s is not None
                    else float("inf"))

        cand_rank = rank(cand)
        victims = [(rank(inf.request), inf.request.uid, s)
                   for s, inf in self._inflight.items()
                   if rank(inf.request) > cand_rank]
        if not victims:
            return
        self.preempt(max(victims)[2])

    def _finish(self, inf: _Inflight, now_s: float,
                results: List[RequestResult]) -> None:
        """Close out a request: metrics and slot release (MOA pricing is
        deferred to the end of ``run`` — it is an O(new_tokens) host loop
        and must not stall the decode ticks of the remaining slots)."""
        m = inf.metrics
        m.finished_s = now_s
        m.new_tokens = len(inf.generated)
        reason = (FinishReason.EOS
                  if inf.generated[-1] == inf.request.eos_id
                  else FinishReason.LENGTH)
        results.append(RequestResult(
            uid=inf.request.uid,
            tokens=np.asarray(inf.generated, np.int32),
            prompt_len=m.prompt_tokens, slot=inf.slot,
            finish_reason=reason, metrics=m))
        if self.paged:
            self._release_paged(inf.slot)
        if self.drafter is not None:
            self.drafter.release(inf.slot)
            self._tick_contexts[inf.request.uid] = inf.tick_contexts
        self.scheduler.release(inf.slot)
        self._inflight.pop(inf.slot, None)

    def _decode_tick(self, results: List[RequestResult]) -> None:
        """One batched decode step over all slots; advance active requests."""
        with TraceAnnotation(tracing.DECODE):
            toks = np.zeros((self.n_slots, 1), np.int32)
            temps = np.zeros((self.n_slots,), np.float32)
            greedy = np.ones((self.n_slots,), bool)
            for slot, inf in self._inflight.items():
                toks[slot, 0] = inf.next_token
                temps[slot] = max(inf.request.sampler.temperature, 0.0)
                greedy[slot] = inf.request.sampler.greedy
            if self.paged:
                hw = self._live_blocks(1)
                decode = self._decode_for(hw)
            else:
                decode = self._decode
            logits, self.cache = decode(self.params, self.cache,
                                        jnp.asarray(toks))
            if self._on_logits is not None:
                self._on_logits(logits[:, -1])
            sampled = self._sample(
                logits[:, -1], jnp.asarray(temps), jnp.asarray(greedy),
                self._next_key())
        with TraceAnnotation(tracing.PULL):
            next_toks = np.asarray(sampled)
        with TraceAnnotation(tracing.COMMIT):
            self._steps += 1
            self._occupancy_sum += len(self._inflight) / self.n_slots
            if self.paged:
                self._block_occ_sum += self._pool.in_use / self.n_blocks
                self._peak_blocks = max(self._peak_blocks,
                                        self._pool.in_use)
                g, f = self._kv_bytes_tick(hw, 1)
                self._gathered_kv_bytes += g
                self._fused_kv_bytes += f
                self._kv_step_log.append((g, f))
            now = self._now(self._t_start)
            for slot in sorted(self._inflight):
                inf = self._inflight[slot]
                tok = int(next_toks[slot])
                inf.generated.append(tok)
                inf.next_token = tok
                if tok == inf.request.eos_id \
                        or len(inf.generated) >= inf.request.max_new_tokens:
                    self._finish(inf, now, results)

    def _spec_tick(self, results: List[RequestResult]) -> None:
        """One speculative tick: draft → verify → accept → commit.

        The drafter proposes ``k`` tokens per active slot; one
        ``verify_step`` scores the pending token plus the draft window,
        writing all ``k + 1`` K/V rows tentatively; the jitted acceptance
        picks each slot's accepted prefix (greedy exact-match or exact
        rejection sampling); the commit advances each slot's cursor by
        ``accepted + 1`` (0 for idle slots), which *is* the rejection
        rollback — rejected rows are masked garbage until overwritten.
        Each slot emits ``accepted + 1`` tokens, the last becoming its
        pending next token.
        """
        with TraceAnnotation(tracing.VERIFY):
            k = self.spec_k
            histories = {
                slot: tuple(inf.request.prompt) + tuple(inf.generated)
                for slot, inf in self._inflight.items()}
            proposals = self.drafter.propose(histories)
            toks = np.zeros((self.n_slots, k + 1), np.int32)
            temps = np.zeros((self.n_slots,), np.float32)
            greedy = np.ones((self.n_slots,), bool)
            for slot, inf in self._inflight.items():
                toks[slot, 0] = inf.next_token
                toks[slot, 1:] = proposals[slot]
                temps[slot] = max(inf.request.sampler.temperature, 0.0)
                greedy[slot] = inf.request.sampler.greedy
            if self.paged:
                hw = self._live_blocks(k + 1)
                verify = self._verify_for(hw)
            else:
                verify = self._verify
            logits, self.cache, aux = verify(self.params, self.cache,
                                             jnp.asarray(toks))
            out, n_acc = self._accept(
                logits, jnp.asarray(toks[:, 1:]), jnp.asarray(temps),
                jnp.asarray(greedy), self._next_key())
        with TraceAnnotation(tracing.PULL):
            out, n_acc = np.asarray(out), np.asarray(n_acc)
        with TraceAnnotation(tracing.COMMIT):
            keep = np.zeros((self.n_slots,), np.int32)
            for slot in self._inflight:
                keep[slot] = n_acc[slot] + 1
            self.cache = self._commit(self.cache, jnp.asarray(keep), aux)
            self._steps += 1
            self._spec_ticks += 1
            self._occupancy_sum += len(self._inflight) / self.n_slots
            self._spec_slot_steps += len(self._inflight)
            if self.paged:
                self._block_occ_sum += self._pool.in_use / self.n_blocks
                self._peak_blocks = max(self._peak_blocks,
                                        self._pool.in_use)
                g, f = self._kv_bytes_tick(hw, k + 1)
                self._gathered_kv_bytes += g
                self._fused_kv_bytes += f
                self._kv_step_log.append((g, f))
            now = self._now(self._t_start)
            for slot in sorted(self._inflight):
                inf = self._inflight[slot]
                inf.tick_contexts.append(
                    inf.request.prompt_len + len(inf.generated) - 1)
                accepted = int(n_acc[slot])
                self._accept_hist[accepted] += 1
                done = False
                for tok in out[slot, : accepted + 1]:
                    tok = int(tok)
                    inf.generated.append(tok)
                    inf.next_token = tok
                    self._spec_emitted += 1
                    if tok == inf.request.eos_id or len(inf.generated) \
                            >= inf.request.max_new_tokens:
                        done = True
                        break
                if done:
                    self._finish(inf, now, results)

    # ---- warmup ------------------------------------------------------------
    def _warmup_tick(self) -> None:
        """Compile the tick-critical callables with throwaway inputs.

        Runs one unmeasured prefill per prompt bucket (padded-prefill
        families — exact-length families still compile per novel prompt
        length at admission), the fixed-shape paged helpers (slot write /
        CoW / release), and one decode / verify tick before the engine
        clock starts, so first-call XLA compile time lands in
        ``compile_s`` instead of skewing ``wall_s`` / TTFT / per-token
        metrics. Not covered (inherently variable-shape): the prefix-hit
        gather and suffix prefill, which compile per distinct (prefix
        blocks, suffix bucket) pair on the first hit. All warmup writes
        are harmless by construction: dense-slot rows are overwritten at
        the next admission, paged writes are redirected to the trash page,
        and a spec commit with ``keep=0`` restores recurrent state from
        the pre-verify snapshot.
        """
        n = self.n_slots
        key = jax.random.PRNGKey(0)     # never draws from the engine stream
        pre = None
        if self._padded:
            for bucket in self.scheduler.buckets:
                toks = np.zeros((1, bucket), np.int32)
                _, pre = self._prefill(self.params, {"tokens": toks},
                                       np.asarray(bucket, np.int32))
        if self.paged and pre is not None:
            kv, state = self.model.split_prefill_cache(pre)
            n_written = kv["k"].shape[2] // self.block_size
            trash = np.full((n_written,), TRASH_BLOCK, np.int32)
            row = np.full((self._max_blocks,), TRASH_BLOCK, np.int32)
            self.cache = self._paged_write(
                self.cache, kv, state, jnp.asarray(trash), jnp.asarray(row),
                0, jnp.asarray(0, jnp.int32))
        elif pre is not None:
            self.cache = self._write(self.cache, pre, 0)
        if self.paged:
            # release + CoW are fixed-shape: compile them on the trash page
            # (copying page 0 onto itself and re-clearing an empty slot are
            # no-ops by construction)
            self.cache = self._cow_copy(self.cache, 0, 0, 0, 0)
            self.cache = self._clear_slot(self.cache, 0)
        if self.drafter is not None:
            toks = np.zeros((n, self.spec_k + 1), np.int32)
            if self.paged:
                # compile every live-block bucket now (a growing batch walks
                # the buckets in order; each is a distinct executable, and a
                # mid-run compile would land in wall_s) — verify + keep=0
                # commit restores the pre-verify cache bit-identically
                toks_j = jnp.asarray(toks)
                keep0 = jnp.zeros((n,), jnp.int32)
                for hw in self._hw_buckets():
                    logits, cache, aux = self._verify_for(hw)(
                        self.params, self.cache, toks_j)
                    self.cache = self._commit(cache, keep0, aux)
            else:
                logits, cache, aux = self._verify(self.params, self.cache,
                                                  jnp.asarray(toks))
                self.cache = self._commit(cache, jnp.zeros((n,), jnp.int32),
                                          aux)
            self._accept(logits, jnp.asarray(toks[:, 1:]),
                         jnp.zeros((n,), jnp.float32),
                         jnp.ones((n,), bool), key)
        else:
            if self.paged:
                # warmup decode writes land on the trash page and idle-slot
                # cursors are reset at admission, so ticking once per bucket
                # is as harmless as ticking once
                toks0 = jnp.zeros((n, 1), jnp.int32)
                for hw in self._hw_buckets():
                    logits, self.cache = self._decode_for(hw)(
                        self.params, self.cache, toks0)
            else:
                logits, self.cache = self._decode(self.params, self.cache,
                                                  jnp.zeros((n, 1),
                                                            jnp.int32))
            self._sample(logits[:, -1], jnp.zeros((n,), jnp.float32),
                         jnp.ones((n,), bool), key)
        jax.block_until_ready(self.cache)

    # ---- public API --------------------------------------------------------
    def submit(self, request: Request) -> None:
        """Queue a request (admitted when arrived, a slot frees up, and —
        paged — the pool can cover its worst-case block need)."""
        if self.paged:
            need = blocks_needed(request.prompt_len,
                                 request.max_new_tokens + self.spec_k,
                                 self.block_size)
            if need > self.n_blocks:
                raise ValueError(
                    f"request {request.uid}: needs {need} blocks but the "
                    f"pool only has {self.n_blocks} — it could never be "
                    "admitted")
        self.scheduler.submit(request)

    def reload_params(self, params) -> None:
        """Swap the weight tree in place (live reload between ticks).

        The new tree must match the current one's structure, shapes, and
        dtypes; on a mesh engine it is ``device_put`` onto the engine's
        parameter shardings. The jitted callables take params as a plain
        (non-donated) argument, so the swap is just a reference change —
        the next prefill/decode tick reads the new weights. In-flight
        slots keep decoding, now against the new weights; callers that
        need every generation pinned to one weight version (the replica
        router's rolling reload) drain the engine first.
        """
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(params)
        if old_def != new_def:
            raise ValueError(
                "reload_params: new weight tree structure differs from the "
                f"serving one ({new_def} vs {old_def})")
        for i, (old, new) in enumerate(zip(old_leaves, new_leaves)):
            if (tuple(old.shape) != tuple(np.shape(new))
                    or old.dtype != np.asarray(new).dtype):
                raise ValueError(
                    f"reload_params: leaf {i} changed layout "
                    f"({np.shape(new)}/{np.asarray(new).dtype} vs "
                    f"{tuple(old.shape)}/{old.dtype}) — a reload may not "
                    "change the architecture")
        if self.mesh is not None:
            params = jax.device_put(params, self._param_sh)
        self.params = params

    def start_run(self, *, warmup: bool = False,
                  t_origin: Optional[float] = None,
                  on_logits: Optional[Callable[[jax.Array], None]] = None
                  ) -> None:
        """Reset per-run counters and start the engine clock.

        Part of the tick-level API (``start_run`` / ``tick`` /
        ``finish_run``) that :meth:`run` is built from and that the replica
        router drives directly. ``t_origin`` pins the clock origin instead
        of reading the clock — the router passes one shared origin so every
        replica (including ones constructed mid-run on revival) reports on
        the same fleet timeline. ``on_logits``, when given, is called with
        each decode tick's ``(n_slots, vocab)`` last-position logits before
        they are sampled (warmup ticks excluded).
        """
        self._compile_s = 0.0
        self._on_logits = None
        if warmup:
            t0 = self._clock()
            self._warmup_tick()
            self._compile_s = self._clock() - t0
        self._on_logits = on_logits
        # per-run counters: a reused engine (submit + repeated run) must not
        # carry stale fast-forward offsets, occupancy sums, or prior-run
        # admissions into its report
        self._steps = 0
        self._occupancy_sum = 0.0
        self._fast_forward_s = 0.0
        if self.drafter is not None:
            self._spec_ticks = 0
            self._spec_emitted = 0
            self._spec_slot_steps = 0.0
            self._accept_hist = [0] * (self.spec_k + 1)
            self._draft_steps_start = self.drafter.draft_steps
            self._tick_contexts: Dict[int, List[int]] = {}
        if self.paged:
            self._prefix_hits = 0
            self._shared_block_hits = 0
            self._cow_count = 0
            self._admissions = 0
            self._block_occ_sum = 0.0
            self._peak_blocks = 0
            self._gathered_kv_bytes = 0
            self._fused_kv_bytes = 0
            self._kv_step_log = []
        self._preemptions = 0
        self._spills = 0
        self._revivals = 0
        self._chunk_ticks = 0
        self._log_start = len(self.scheduler.admission_log)
        self._t_start = self._clock() if t_origin is None else t_origin

    def tick(self, results: List[RequestResult]) -> None:
        """One scheduling tick: admit what arrived, advance one prefill
        chunk set, one decode/verify step. Appends newly finished requests
        to ``results``. No-op when the scheduler has no work (so a router
        may tick an idle replica safely)."""
        if self.scheduler.done:
            return
        with TraceAnnotation(tracing.TICK):
            now = self._now(self._t_start)
            if not self.scheduler.active and not self.scheduler.has_ready \
                    and self.scheduler.next_arrival_s > now:
                # idle: fast-forward the engine clock to the next arrival
                # (a gate-vetoed head sits in the ready queue, so has_ready
                # guards against fast-forwarding past work that only needs
                # blocks, not time)
                self._fast_forward_s += self.scheduler.next_arrival_s - now
                now = self._now(self._t_start)
            if self.scheduling == "slo":
                with TraceAnnotation(tracing.SCHEDULE):
                    self._maybe_preempt(now)
            gate = self._admission_gate if self.paged else None
            while True:
                # one at a time so each admission's block allocation is
                # visible to the next gate evaluation
                with TraceAnnotation(tracing.SCHEDULE):
                    admitted = self.scheduler.admit_ready(now, gate=gate,
                                                          limit=1)
                if not admitted:
                    break
                self._admit(admitted[0][0], admitted[0][1], now, results)
            if self.paged and not self._inflight and not self._prefilling \
                    and self._spilled:
                # stall escape: every runnable request is spilled but the
                # gate vetoes the (fresh) ready head — revive a spilled one
                # out of order; it holds its reservation, so it always fits
                with TraceAnnotation(tracing.SCHEDULE):
                    got = self.scheduler.admit_revivable(now,
                                                         set(self._spilled))
                if got is not None:
                    self._admit(got[0], got[1], now, results)
            if self._prefilling:
                with TraceAnnotation(tracing.PREFILL_CHUNK):
                    self._prefill_tick(results)
            if self._inflight:
                if self.drafter is not None:
                    self._spec_tick(results)
                else:
                    self._decode_tick(results)

    def run(self, requests: Sequence[Request] = (),
            max_steps: Optional[int] = None, *, warmup: bool = False,
            on_logits: Optional[Callable[[jax.Array], None]] = None
            ) -> Tuple[List[RequestResult], dict]:
        """Serve until every submitted request completes.

        Returns ``(results sorted by uid, report)`` where ``report`` is the
        JSON-able aggregate from :func:`repro.serve.metrics.aggregate` plus
        ``slot_reuse`` (admissions into a previously-used slot this run)
        and — paged — a ``paged`` sub-report (block occupancy, prefix-hit
        rate, resident bytes). ``max_steps`` is a runaway backstop, not a
        budget: exceeding it raises RuntimeError (default 1e6 decode
        ticks).

        ``warmup=True`` executes one throwaway prefill + decode/verify tick
        *before* the engine clock starts, so first-call XLA compilation
        lands in the report's ``compile_s`` instead of inflating
        ``wall_s`` / TTFT / ``tok_per_s`` (a warm engine pays ~0 here).
        ``on_logits`` observes each decode tick's logits
        (:meth:`start_run`).
        """
        self.start_run(warmup=warmup, on_logits=on_logits)
        for r in requests:
            self.submit(r)
        results: List[RequestResult] = []
        limit = max_steps if max_steps is not None else 1_000_000
        while not self.scheduler.done:
            self.tick(results)
            if self._steps + self._chunk_ticks >= limit:
                raise RuntimeError(
                    f"serve engine exceeded {limit} decode steps with "
                    f"{len(self._inflight)} requests still in flight")
        return self.finish_run(results)

    def finish_run(self, results: List[RequestResult]
                   ) -> Tuple[List[RequestResult], dict]:
        """Price completed requests and build the run report; the closing
        half of the tick-level API."""
        compile_s = self._compile_s
        log_start = self._log_start
        wall = self._now(self._t_start)
        for r in results:
            if self.drafter is not None:
                # acceptance-aware: every (k+1)-token verify pass this
                # request sat through is compute spent, accepted or not
                r.metrics.moa_flops = spec_request_decode_cost(
                    self.model.cfg, k=self.spec_k,
                    tick_contexts=self._tick_contexts.get(r.uid, ()))
            else:
                r.metrics.moa_flops = request_decode_cost(
                    self.model.cfg, prompt_tokens=r.metrics.prompt_tokens,
                    new_tokens=r.metrics.new_tokens)
        report = aggregate(results, n_slots=self.n_slots,
                           decode_steps=self._steps,
                           occupancy_sum=self._occupancy_sum, wall_s=wall,
                           compile_s=compile_s)
        report["slot_reuse"] = self.scheduler.slot_reuse_count(log_start)
        report["arch"] = self.model.cfg.name
        report["moa"] = self.model.cfg.moa_strategy.spec
        report["scheduling"] = self.scheduling
        if self.scheduling == "slo" or any(
                r.metrics.deadline_s is not None for r in results):
            report["slo"] = slo_report(
                results, wall_s=wall, preemptions=self._preemptions,
                spills=self._spills, revivals=self._revivals,
                prefill_chunk_tokens=self._chunk or 0,
                prefill_chunk_count=self._chunk_ticks)
        if self.drafter is not None:
            report["spec"] = spec_report(
                k=self.spec_k, verify_ticks=self._spec_ticks,
                emitted_tokens=self._spec_emitted,
                slot_steps=self._spec_slot_steps,
                accepted_hist=self._accept_hist,
                draft_steps=self.drafter.draft_steps
                - self._draft_steps_start)
        if self.paged:
            report["paged"] = paged_report(
                spec=self._spec, n_slots=self.n_slots, max_len=self.max_len,
                block_size=self.block_size, n_blocks=self.n_blocks,
                admissions=self._admissions, prefix_hits=self._prefix_hits,
                shared_block_hits=self._shared_block_hits,
                cow_count=self._cow_count,
                block_occ_sum=self._block_occ_sum, decode_steps=self._steps,
                peak_blocks=self._peak_blocks,
                attn_backend=resolve_attn_backend(self.model.cfg.attn_backend),
                gathered_kv_bytes=self._gathered_kv_bytes,
                fused_kv_bytes=self._fused_kv_bytes)
        results.sort(key=lambda r: r.uid)
        return results, report
