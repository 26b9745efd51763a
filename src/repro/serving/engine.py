"""Real-execution serving engine: continuous batching over slotted or PAGED
KV caches, driven through the unified request/response API.

This is the end-to-end validation path for Clover on this CPU container: the
variants are reduced-config LMs (a real quality ladder — fewer layers →
measurably lower quality and lower latency/energy), instances map to "slices"
(on CPU every slice is the host device; the slice size feeds the energy
model), and the Clover controller drives reconfiguration exactly as it would
on a pod.  Examples/serve_clover.py runs the full loop.

Two KV layouts share the serving loop (``RealEngine(kv_layout=...)``):

  * ``"slotted"`` (PR 2) — every ``Instance`` owns a fixed-capacity batched
    cache (``models.registry.make_slot_cache``): ``n_slots`` sequences, each
    reserving ``max_len`` tokens regardless of its prompt, per-slot valid-
    prefix ``lengths`` masking (``kernels/decode_attention.py`` contract);
  * ``"paged"`` (PR 3) — every ``PagedInstance`` owns one block **arena**
    (``models.registry.make_block_arena``) mapped by the ``serving.kvpool``
    allocator: sequences hold exactly the fixed-size blocks their tokens
    need, **admission is by block availability** (not slot count), a radix
    **prefix cache** (``kvpool.prefix``) lets requests share common prompt-
    prefix blocks by refcount, prefill is **chunked** (long prompts advance
    one chunk per tick, interleaved with decode, so occupied sequences never
    stall behind a long admission), and attention gathers K/V through block
    tables (``kernels/paged_attention.py``; ``kernels/ref.py`` on CPU).

The serving surface is the ``ServingBackend`` protocol (``serving.api``):
``submit`` typed :class:`InferenceRequest`s, ``step`` one scheduler tick,
``drain`` to completion, ``stats`` for the session aggregates.  On top:

  * **pluggable admission** (``serving.policies``): FIFO (bit-identical to
    the PR 2/3 behavior), priority, EDF over deadlines, and the carbon-aware
    two-class policy, all layered on the shared ``SchedulerCore``.  A failed
    block-aware admission is **gated**: the engine only re-attempts once the
    instance's free capacity (slots / free+evictable blocks) or the queue
    head actually changed, instead of re-peeking every tick;
  * **per-request attribution**: every decode tick's occupancy-scaled energy
    is split over the rows that held the batch, prefill chunks are charged
    to the prefilling request, the session's idle floor is spread across its
    responses — so per-request joules sum to the engine total, and
    ``carbon_g = joules × ci_g_per_kwh`` is a per-request quantity the fleet
    layer can aggregate (EcoServe-style attribution);
  * **paged preemption** (``preemption=True``): admission reserves only the
    prompt's blocks and decode grows block tables on demand; when the arena
    runs dry mid-decode the engine victim-selects the lowest-priority /
    youngest sequence, swaps its K/V blocks to HOST memory, re-queues it,
    and restores it bit-exactly on re-admission — greedy outputs are
    preemption-invariant, replacing the conservative whole-sequence
    reservation;
  * **policy-aware prefill queue** (paged): inside a tick's chunked-prefill
    burst budget the active policy orders the instance's prefill queue, so
    an interactive admission's chunks preempt a long background prefill
    mid-prompt instead of queueing behind it in admission order;
  * **partial swap-in**: a preempted sequence whose prompt blocks the radix
    tree still holds is restored by re-referencing those pages and copying
    back only the evicted tail (``partial_swapin_pages_saved`` in stats) —
    still bit-exact, a tree eviction just degrades to the full restore;
  * **open-loop serving**: requests with ``arrival_s`` release on a wall-
    clock schedule (``serve_poisson`` draws one), so queueing delay and TTFT
    are measured at sub-saturation loads instead of only closed-batch
    makespan;
  * energy per decode tick scales with row occupancy
    (``PM.instance_power_w(chips, occupied / capacity)``); prefill work is
    charged at full busy power; unaccounted wall time draws idle power;
  * ``configure`` is **warm**: instances pool by (variant, chips) and jitted
    functions live on the ``EngineVariant``; ``warmup`` compiles exactly the
    shape set the serve loop can reach (``serve_buckets``) so a probe
    window's first token never pays a trace.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perf_model as PM
from repro.core.catalog import Variant
from repro.models import registry as R
from repro.models.config import ModelConfig
from repro.obs import MetricsRegistry, PhaseProfiler, Telemetry, TraceRecorder
from repro.serving.api import DONE, InferenceRequest, InferenceResponse, \
    serve_prompts
from repro.serving.kvpool import BlockAllocator, RadixPrefixCache
from repro.serving.policies import SchedulerPolicy, make_policy
from repro.serving.quality import make_selector
from repro.serving.scheduler import SchedulerCore, latency_percentile

__all__ = ["latency_percentile", "EngineVariant", "build_engine_family",
           "Instance", "PagedInstance", "RealEngine", "serve_buckets"]


@dataclasses.dataclass
class EngineVariant:
    variant: Variant
    cfg: ModelConfig
    params: dict
    # jitted entry points, shared by every Instance of this variant (warm
    # reconfiguration: re-instantiating an instance never re-traces)
    fns: dict = dataclasses.field(default_factory=dict, repr=False)


def build_engine_family(base_cfg: ModelConfig, fracs=(1.0, 0.5, 0.25),
                        seed: int = 0) -> List[EngineVariant]:
    """Instantiate a real quality ladder by depth reduction.  Parameters are
    drawn by one jitted program per rung: op-by-op initialisation of the
    full-width ladder spent minutes dispatching on the chip."""
    out = []
    for i, frac in enumerate(sorted(fracs)):
        n_layers = max(int(base_cfg.n_layers * frac), 1)
        cfg = base_cfg.with_(n_layers=n_layers,
                             name=f"{base_cfg.name}-x{frac:g}")
        params = jax.jit(R.init_params, static_argnums=1)(
            jax.random.PRNGKey(seed), cfg)
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        v = Variant(family=base_cfg.name, name=f"x{frac:g}", quality=i + 1,
                    accuracy=0.80 + 0.05 * i, flops_g=n_params * 2 / 1e9,
                    params_m=n_params / 1e6, mem_gb=n_params * 4 / 2**30 + 0.1)
        out.append(EngineVariant(v, cfg, params))
    return out


def _write_slot(cache_k, cache_v, lengths, k_all, v_all, slot, true_len):
    """Write one prefill's K/V into a slot and set its length (jitted so the
    two cache updates fuse into one dispatch; slot/true_len stay traced).
    K/V arrive in the model's dtype and are stored in the cache's."""
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k_all.astype(cache_k.dtype), (0, slot, 0, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v_all.astype(cache_v.dtype), (0, slot, 0, 0, 0))
    return cache_k, cache_v, lengths.at[slot].set(true_len)


def _variant_fns(ev: EngineVariant) -> dict:
    """Jitted prefill/decode for one variant, built once and cached on the
    EngineVariant (jax's jit cache then handles per-shape specialisation)."""
    if "prefill" not in ev.fns:
        cfg = ev.cfg

        # named functions, so the XLA modules are ``jit_prefill`` etc.
        def prefill(p, t):
            return R.prefill_kv(p, {"tokens": t}, cfg)

        def decode(p, c, t, a):
            return R.decode_slots(p, c, {"tokens": t}, cfg, a)

        ev.fns["prefill"] = jax.jit(prefill)
        ev.fns["decode"] = jax.jit(decode)
        ev.fns["write"] = jax.jit(_write_slot)
    return ev.fns


def _paged_fns(ev: EngineVariant) -> dict:
    """Jitted chunked-prefill / paged-decode entry points (same per-variant
    sharing discipline as ``_variant_fns``).  The arena, ``{"k","v"}`` each
    ``(L, n_blocks, bs, K·dh)``, is DONATED: every call scatters a handful
    of K/V rows into a buffer of gigabytes — without donation XLA copies
    the whole arena per step, and the copy dominates the decode tick on
    large pools.  Callers must treat the passed-in arena as consumed (the
    instance reassigns from the result).

    ``decode_multi`` is the device-resident hot path: ``k`` fused greedy
    steps (static — one compile per (bucket, k)) with on-device argmax
    feedback, returning the advanced ``next``/``lengths`` loop buffers so
    steady-state decode never uploads host state.  ``next`` and ``lengths``
    are donated alongside the arena (updated in place); ``tables`` and the
    active mask are reused read-only across ticks.  ``restore_paged`` /
    ``gather_pages`` are the swap staging pair over the block dim (images
    ``(L, n_pages, bs, K·dh)``): a donated in-place page scatter for
    swap-in (the un-jitted ``.at[].set`` copied the whole arena) and a page
    gather whose result is copied device→host asynchronously at swap-out.
    ``first_token`` (a final chunk's last-position argmax) and ``set_next``
    (its scatter into an uploaded ``next`` buffer) are the small steps
    between them.

    Every entry is a named function, so each XLA module carries its name
    (``jit_prefill_paged``, ``jit_decode_multi``, ...) into the device
    trace."""
    if "prefill_paged" not in ev.fns:
        cfg = ev.cfg

        def prefill_paged(p, t, ar, tb, np_, tc):
            return R.prefill_paged(p, {"tokens": t}, cfg, ar, tb, np_, tc)

        def decode_paged(p, ar, t, tb, ln, act):
            return R.decode_paged(p, ar, {"tokens": t}, cfg, tb, ln, act)

        def decode_multi(p, ar, t, tb, ln, act, k):
            return R.decode_paged_multi(p, ar, {"tokens": t}, cfg, tb, ln,
                                        act, k)

        def restore_paged(ar, idx, hk, hv):
            return {"k": ar["k"].at[:, idx].set(hk),
                    "v": ar["v"].at[:, idx].set(hv)}

        def gather_pages(ar, idx):
            return ar["k"][:, idx], ar["v"][:, idx]

        def first_token(logits, i):
            return jnp.argmax(logits[0, i]).astype(jnp.int32)

        def set_next(nxt, row, tok):
            return nxt.at[row, 0].set(tok)

        ev.fns["prefill_paged"] = jax.jit(prefill_paged, donate_argnums=(2,))
        ev.fns["decode_paged"] = jax.jit(decode_paged, donate_argnums=(1,))
        ev.fns["decode_multi"] = jax.jit(
            decode_multi, static_argnames=("k",), donate_argnums=(1, 2, 4))
        ev.fns["restore_paged"] = jax.jit(restore_paged, donate_argnums=(0,))
        ev.fns["gather_pages"] = jax.jit(gather_pages)
        ev.fns["first_token"] = jax.jit(first_token)
        ev.fns["set_next"] = jax.jit(set_next)
    return ev.fns


def _sharded_params(ev: EngineVariant, mesh) -> dict:
    """Mesh-sharded copy of a variant's params, cached on the EngineVariant
    per mesh (same sharing discipline as the jitted fns: every instance of
    the variant on the same mesh reuses one device_put, so warm
    reconfiguration never re-places weights)."""
    key = ("sharded_params", id(mesh))
    if key not in ev.fns:
        ev.fns[key] = R.shard_params(ev.params, ev.cfg, mesh)
    return ev.fns[key]


def _bucket(n: int) -> int:
    """Prompt padding bucket (next power of two, floor 8) so prefill jit
    specialisations stay bounded as prompt lengths vary."""
    b = 8
    while b < n:
        b *= 2
    return b


def _pow2_bucket(n: int, cap: int) -> int:
    """Smallest power of two ≥ n, clamped to ``cap`` — ALWAYS a member of
    ``_bucket_ladder(cap)``, so a shape chosen at serve time is guaranteed
    to be one that warmup compiled."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _bucket_ladder(cap: int) -> List[int]:
    """All values ``_pow2_bucket`` can produce for a given cap: powers of
    two below it, plus the cap itself.  Warmup walks exactly this ladder."""
    out: List[int] = []
    b = 1
    while b < cap:
        out.append(b)
        b *= 2
    out.append(cap)
    return out


def serve_buckets(max_len: int) -> List[int]:
    """Every prompt bucket the serve loop can reach on a cache of
    ``max_len``: admitted prompts have ``true_len <= max_len - n_new <=
    max_len - 1``, so the reachable set is exactly
    ``{_bucket(n) for n in 1..max_len-1}``.

    ``Instance.warmup`` compiles precisely this set — a missed bucket means
    the first real request at that length pays a jit trace (polluting a
    probe window's measured first-token latency), an extra bucket is wasted
    cold-``configure`` compile time.  Keeping the walk next to ``_bucket``
    is what makes the two definitions impossible to drift apart."""
    out: List[int] = []
    b = 8
    while True:
        out.append(b)
        if b >= max_len - 1:
            break
        b *= 2
    return out


@dataclasses.dataclass
class _SlotState:
    """Host-side request state of one occupied slot."""
    rid: int
    t_arrival: float
    remaining: int                 # decode steps still to run
    tokens: List[int]              # generated token ids (prefill token first)
    t_first: Optional[float] = None   # host time the first token was read
    t_prefilled: Optional[float] = None   # host time prefill was dispatched
    priority: int = 0
    preempts: int = 0              # slotted sequences never preempt (uniform
                                   # field so the engine reads one shape)


@dataclasses.dataclass
class _SwapState:
    """Host-side image of a preempted paged sequence: everything needed to
    restore it bit-exactly — request identity, generated tokens, the next
    decode token, and the K/V contents of the blocks it held (``n_ctx``
    valid positions).  Restoring writes the pages back into freshly
    allocated arena blocks, so greedy decode continues on identical state
    and outputs are preemption-invariant.

    ``tree_blocks`` records how many of the sequence's leading pages were
    radix-tree-resident at swap-out (full prompt blocks the prefix cache
    still holds).  On re-admission those pages are re-acquired from the
    tree instead of copied from ``host_k``/``host_v`` — a PARTIAL swap-in
    that restores only the evicted tail.  The host image still covers every
    page, so a tree eviction between swap-out and resume just degrades back
    to a full restore.

    The device→host copy is STAGED: ``img_k``/``img_v`` start as device
    arrays (a jitted page gather) with an async host copy already in
    flight, so swap-out never blocks the decode loop — the transfer
    overlaps subsequent decode ticks and only materialises as numpy when
    ``host_k``/``host_v`` are first read (normally at resume, after the
    copy has long landed)."""
    rid: int
    t_arrival: float
    prompt: np.ndarray
    n_new: int
    priority: int
    tokens: List[int]
    remaining: int
    n_ctx: int                     # K/V positions already in the arena
    next_token: int
    t_first: Optional[float]
    t_prefilled: Optional[float]
    cached_tokens: int
    preempts: int
    img_k: object                  # (L, >=n_blocks, bs, K·dh) device or np
    img_v: object
    nb: int                        # pages actually used (img may be padded)
    tree_blocks: int = 0           # leading pages tree-backed at swap-out
    slo: str = "interactive"
    deadline_s: Optional[float] = None

    @property
    def n_blocks(self) -> int:
        return self.nb

    @property
    def host_k(self) -> np.ndarray:
        """(L, n_blocks, bs, K·dh) host image — materialises (and caches)
        the staged device copy on first read."""
        if not isinstance(self.img_k, np.ndarray):
            self.img_k = np.asarray(self.img_k)[:, :self.nb]
        return self.img_k

    @property
    def host_v(self) -> np.ndarray:
        if not isinstance(self.img_v, np.ndarray):
            self.img_v = np.asarray(self.img_v)[:, :self.nb]
        return self.img_v


@dataclasses.dataclass
class _PendingDecode:
    """One dispatched-but-not-landed decode call of the pipelined loop: the
    (k, B) greedy-token device array (async host copy already in flight),
    the dispatch-time (seq, row) snapshot that maps token columns back to
    sequences, and enough accounting to charge the work when it lands.
    Landing in a LATER tick than ``tick_id`` means the readback overlapped
    a full tick of host work (free); landing in the same tick is a forced
    flush and counts as a ``host_syncs`` blocking round-trip."""
    toks: object                          # (k, B) i32 device array
    rows: List[Tuple["_PagedSeq", int]]   # (seq, dispatch-time row)
    k: int
    t_dispatch: float                     # host stamps of the launch
    dispatch_s: float
    tick_id: int


@dataclasses.dataclass
class _PendingFirst:
    """A prefill's first generated token, still on device: the final
    chunk's last-position argmax with an async host copy in flight.  The
    device scalar is scattered into the uploaded ``next`` buffer whenever
    loop state is pushed (so decode never waits on its value); the host
    only reads it to record ``seq.tokens[0]`` — one tick later, after the
    decode calls dispatched before that tick, or immediately (a counted
    sync) when the request is n_new == 1."""
    seq: "_PagedSeq"
    tok: object                           # () i32 device array
    tick_id: int


def _tick_info(prefill_s: float = 0.0, decode_s: float = 0.0,
               blocks_in_use: int = 0, prefill_rids=None, decodes=None,
               emitted=None, preempted=None) -> Dict[str, object]:
    return {"prefill_s": prefill_s, "decode_s": decode_s,
            "blocks_in_use": blocks_in_use,
            "prefill_rids": prefill_rids or [],   # [(rid, t0, t1), ...]
            # one per landed dispatch: (t_launch, t_landed, k, rids); its
            # rids are the rows that shared it
            "decodes": decodes or [],
            "emitted": emitted or [],             # [(rid, token), ...]
            "preempted": preempted or []}         # [_SwapState, ...]


def _note_shape(inst, key: Tuple) -> None:
    """Compile-retrace accounting: every jitted entry the serve loop hits
    registers its shape key here; a key not pre-seeded by ``warmup``
    (``inst._shapes``) is a post-warmup jit trace — the exact event the
    bucket ladders exist to prevent — and increments the instance's
    lifetime ``retraces`` counter (sessions report the delta)."""
    if key not in inst._shapes:
        inst._shapes.add(key)
        inst.retraces += 1


# phase profiler of instances constructed outside a RealEngine: it
# annotates the device trace but observes no histogram (registry=None);
# the engine overrides ``inst.profiler`` with its own at configure()
_NULL_PROFILER = PhaseProfiler(annotate=jax.profiler.TraceAnnotation)


class Instance:
    """One serving instance: a slotted batched KV cache plus the variant's
    shared jitted one-pass prefill and batched decode step."""

    profiler: PhaseProfiler = _NULL_PROFILER
    role: str = "both"                   # slotted instances never split

    def __init__(self, ev: EngineVariant, chips: int, n_slots: int = 4,
                 max_len: int = 96):
        self.ev = ev
        self.chips = chips
        self.n_slots = n_slots
        self.max_len = max_len
        self._fns = _variant_fns(ev)
        self.cache = R.make_slot_cache(ev.cfg, n_slots, max_len,
                                       dtype=jnp.float32)
        self.slots: List[Optional[_SlotState]] = [None] * n_slots
        self._next = np.zeros((n_slots, 1), np.int32)   # next decode token
        self._shapes: set = set()        # jit shape keys seen (see _note_shape)
        self.retraces = 0                # lifetime post-warmup shape misses
        # host↔device traffic (lifetime; sessions report deltas): the
        # slotted loop is synchronous by design — 2 uploads + 1 blocking
        # readback per decode step — which is exactly the baseline the
        # paged pipelined loop is measured against
        self.host_syncs = 0
        self.h2d_transfers = 0
        self.decode_dispatches = 0

    # --- lifecycle -----------------------------------------------------------
    def reset(self) -> None:
        """Recycle from the warm pool: clear per-slot state.  Cache contents
        are stale but masked out (lengths = 0) until the next prefill."""
        self.cache["lengths"] = jnp.zeros((self.n_slots,), jnp.int32)
        self.slots = [None] * self.n_slots
        self._next[:] = 0

    def warmup(self) -> None:
        """Trigger jit compilation at EXACTLY the shapes the serve loop can
        reach — every prompt bucket from ``serve_buckets`` plus one decode
        step — so cold ``configure`` bears the whole compile cost and the
        first real request never re-jits (a probe window's measured
        first-token latency must not include a trace)."""
        for b in serve_buckets(self.max_len):
            self._shapes.add(("prefill", b))
            dummy = np.zeros((1, b), np.int32)
            lg, k_all, v_all = self._fns["prefill"](self.ev.params,
                                                    jnp.asarray(dummy))
            lg.block_until_ready()
            w = min(b, self.max_len)
            # zero-write into slot 0 at length 0: compiles the slot writer
            # for this bucket without touching logical state
            self.cache["k"], self.cache["v"], self.cache["lengths"] = \
                self._fns["write"](self.cache["k"], self.cache["v"],
                                   self.cache["lengths"], k_all[:, :, :w],
                                   v_all[:, :, :w], 0, 0)
        self._shapes.add(("decode",))
        logits, _ = self._fns["decode"](
            self.ev.params, self.cache, jnp.asarray(self._next),
            jnp.zeros((self.n_slots,), bool))
        logits.block_until_ready()

    # --- slot management -----------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def occupied(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def capacity(self) -> int:
        return self.n_slots

    @property
    def busy(self) -> bool:
        return self.occupied > 0

    def admission_signature(self) -> Tuple:
        """Free-capacity fingerprint for admission gating: a failed admission
        is only re-attempted once this changes (a slot was freed)."""
        return (len(self.free_slots()),)

    # --- serving -------------------------------------------------------------
    def can_admit(self, prompt_len: int, n_new: int) -> bool:
        assert prompt_len + n_new <= self.max_len, \
            f"prompt {prompt_len} + n_new {n_new} > max_len {self.max_len}"
        return any(s is None for s in self.slots)

    def admit_next(self, rid: int, t_arrival: float, prompt: np.ndarray,
                   n_new: int, priority: int = 0, slo: str = "interactive",
                   deadline_s: Optional[float] = None
                   ) -> Tuple[_SlotState, float]:
        """Admit into the first free slot; returns (state, prefill seconds)
        — the engine charges prefill at full busy power.  ``slo`` /
        ``deadline_s`` are accepted for the uniform instance surface; the
        slotted layout prefills at admission, so there is no prefill queue
        for a policy to order."""
        slot = self.free_slots()[0]
        with self.profiler.span("prefill_chunk", rid=rid) as sp:
            state = self.admit(slot, rid, t_arrival, prompt, n_new,
                               priority=priority)
        return state, sp.seconds

    def admit(self, slot: int, rid: int, t_arrival: float,
              prompt: np.ndarray, n_new: int, priority: int = 0
              ) -> _SlotState:
        """One-pass prefill of ``prompt`` into ``slot``.  The prompt's
        last-position logits yield the first generated token immediately —
        the prefill forward is never discarded.  The state is stamped when
        the prefill is dispatched (``t_prefilled``) and when the first
        token's value reaches the host (``t_first``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        true_len = int(prompt.shape[0])
        assert true_len + n_new <= self.max_len, \
            f"prompt {true_len} + n_new {n_new} > max_len {self.max_len}"
        pad = _bucket(true_len)
        _note_shape(self, ("prefill", pad))
        padded = np.zeros((1, pad), np.int32)
        padded[0, :true_len] = prompt
        self.h2d_transfers += 1
        logits, k_all, v_all = self._fns["prefill"](self.ev.params,
                                                    jnp.asarray(padded))
        write = min(pad, self.max_len)   # padded tail beyond capacity is junk
        self.cache["k"], self.cache["v"], self.cache["lengths"] = \
            self._fns["write"](self.cache["k"], self.cache["v"],
                               self.cache["lengths"], k_all[:, :, :write],
                               v_all[:, :, :write], slot, true_len)
        self.host_syncs += 1             # blocking first-token readback
        t_prefilled = time.perf_counter()
        first = int(jnp.argmax(logits[0, true_len - 1]))
        state = _SlotState(rid, t_arrival, remaining=n_new - 1,
                           tokens=[first], t_first=time.perf_counter(),
                           t_prefilled=t_prefilled, priority=priority)
        self._next[slot, 0] = first
        if state.remaining > 0:
            self.slots[slot] = state
        return state

    def step(self) -> Tuple[List[_SlotState], List[Tuple[int, int]]]:
        """One batched decode step over ALL slots; returns (completed
        requests — their slots are freed for mid-flight admission — and the
        (rid, token) emissions of every active row for streaming)."""
        active = np.array([s is not None for s in self.slots])
        _note_shape(self, ("decode",))
        self.h2d_transfers += 2          # next-token + active-mask uploads
        with self.profiler.span("decode_dispatch", k=1,
                                rows=int(active.sum())):
            logits, self.cache = self._fns["decode"](
                self.ev.params, self.cache, jnp.asarray(self._next),
                jnp.asarray(active))
        self.host_syncs += 1             # blocking per-step token readback
        self.decode_dispatches += 1
        with self.profiler.span("decode_land", sync=True):
            toks = np.asarray(jnp.argmax(logits, axis=-1))
        finished: List[_SlotState] = []
        emitted: List[Tuple[int, int]] = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            s.tokens.append(int(toks[i]))
            emitted.append((s.rid, int(toks[i])))
            s.remaining -= 1
            self._next[i, 0] = int(toks[i])
            if s.remaining <= 0:
                finished.append(s)
                self.slots[i] = None
        return finished, emitted

    def tick(self, now: Optional[float] = None, allow_fused: bool = True
             ) -> Tuple[List[_SlotState], Dict[str, object]]:
        """One scheduler tick = one batched decode step (slotted prefill
        runs at admission; ``now`` / ``allow_fused`` are unused here —
        uniform tick surface with :class:`PagedInstance`)."""
        occ = self.occupied
        if occ == 0:
            return [], _tick_info()
        rids = [s.rid for s in self.slots if s is not None]
        t1 = time.perf_counter()
        finished, emitted = self.step()
        t2 = time.perf_counter()
        return finished, _tick_info(decode_s=t2 - t1,
                                    decodes=[(t1, t2, 1, rids)],
                                    emitted=emitted)

    def generate(self, prompt: np.ndarray, n_new: int = 8
                 ) -> Tuple[np.ndarray, float]:
        """Greedy generation for a (possibly batched) prompt.

        prompt: (b, s) int32.  Returns (tokens (b, n_new), wall seconds).
        One-pass prefill + batched decode; each row takes its own argmax
        (the old engine hard-coded ``lg[0]`` and a scalar token feed, so
        every row beyond the first decoded row 0's tokens)."""
        t0 = time.perf_counter()
        prompt = np.asarray(prompt, np.int32)
        b, s = prompt.shape
        fns = self._fns
        logits, k_all, v_all = fns["prefill"](self.ev.params,
                                              jnp.asarray(prompt))
        max_len = s + n_new
        K, dh = self.ev.cfg.n_kv_heads, self.ev.cfg.d_head
        L = self.ev.cfg.n_layers
        cache = {
            "k": jnp.zeros((L, b, max_len, K, dh), jnp.float32
                           ).at[:, :, :s].set(k_all.astype(jnp.float32)),
            "v": jnp.zeros((L, b, max_len, K, dh), jnp.float32
                           ).at[:, :, :s].set(v_all.astype(jnp.float32)),
            "lengths": jnp.full((b,), s, jnp.int32),
        }
        active = jnp.ones((b,), bool)
        tok = jnp.argmax(logits[:, s - 1], axis=-1)          # (b,) per-row
        out = [tok]
        for _ in range(n_new - 1):
            lg, cache = fns["decode"](self.ev.params, cache,
                                      tok[:, None].astype(jnp.int32), active)
            tok = jnp.argmax(lg, axis=-1)
            out.append(tok)
        toks = np.asarray(jnp.stack(out, axis=1))
        return toks, time.perf_counter() - t0


# =============================================================================
# paged instance (kvpool)
# =============================================================================
@dataclasses.dataclass
class _PagedSeq:
    """Host-side state of one sequence in a paged instance.

    Carries the request's scheduling metadata (``priority``/``slo``/
    ``deadline_s``) plus a stable admission counter ``seq``, matching the
    attribute contract of ``scheduler._Entry`` — so the engine's active
    :class:`~repro.serving.policies.SchedulerPolicy` can order the
    instance-level chunked-prefill queue with the same ``select`` it uses
    for admission."""
    rid: int
    t_arrival: float
    prompt: np.ndarray
    n_new: int
    row: int                        # batch row (static decode shape)
    blocks: List[int]               # owned block refs (shared prefix + fresh)
    n_done: int                     # prompt tokens whose K/V are in the arena
    cached_tokens: int              # prefix-cache hit size at admission
    remaining: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_prefilled: Optional[float] = None   # last prefill chunk dispatched
    t_first: Optional[float] = None       # first token's value on the host
    priority: int = 0
    preempts: int = 0               # times this sequence was swapped out
    pending_steps: int = 0          # decode steps dispatched but not landed
                                    # (``remaining`` is decremented at
                                    # DISPATCH; completion waits for landing)
    pending_first: Optional["_PendingFirst"] = None
    slo: str = "interactive"
    deadline_s: Optional[float] = None
    seq: int = 0                    # admission order (policy tie-break)

    @property
    def prefilled(self) -> bool:
        return self.n_done >= len(self.prompt)


class PagedInstance:
    """One serving instance over a paged KV arena.

    Memory is ``n_blocks`` fixed-size blocks (``kvpool.BlockAllocator`` owns
    the map); a sequence holds exactly ``ceil((prompt+n_new)/block_size)``
    blocks, minus whatever the radix prefix cache already has.  The decode
    batch is ``max_seqs`` static rows; admission is bounded by *blocks*, not
    rows — short prompts pack far more concurrency into the same arena than
    the slotted cache's per-slot ``max_len`` reservation.

    With ``preemption=True`` the whole-sequence reservation is dropped:
    admission reserves only the PROMPT's blocks and decode grows each
    sequence's table on demand; when the arena runs dry mid-decode the
    lowest-priority / youngest sequence is swapped out to host memory
    (``_SwapState``) for the engine to re-queue and later restore
    bit-exactly.

    With ``mesh`` the instance is SHARDED: params are placed under the
    GSPMD rules (tensor-parallel attention/MLP over "model"), the arena is
    committed with KV heads over "model" (``sharding.rules.arena_spec`` —
    an explicit error for non-divisible head counts), and uploaded loop
    buffers shard their row dim over "data" when divisible.  Block tables
    and the allocator stay host-side; the pipelined loop, fused dispatch
    and donation discipline are unchanged — jit just specializes to the
    sharded layouts.

    ``role`` splits the serving loop for disaggregation (``serving.
    disagg``): a ``"prefill"`` worker runs chunked prefill only (its tick
    never dispatches decode, admission reserves prompt blocks only) and
    fully-prefilled sequences are extracted via :meth:`handoff_out`; a
    ``"decode"`` worker receives them through ``resume``.  The default
    ``"both"`` is the monolithic engine."""

    profiler: PhaseProfiler = _NULL_PROFILER

    def __init__(self, ev: EngineVariant, chips: int, n_blocks: int,
                 block_size: int = 16, max_seqs: int = 8, max_len: int = 96,
                 chunk_blocks: int = 2, prefix_caching: bool = True,
                 cache_watermark: float = 0.25, chunk_burst: int = 4,
                 preemption: bool = False,
                 policy: Optional[SchedulerPolicy] = None,
                 pipeline: bool = True, fused_steps: int = 8,
                 mesh=None, role: str = "both"):
        assert role in ("both", "prefill", "decode"), role
        self.ev = ev
        self.chips = chips
        self.mesh = mesh
        self.role = role
        self.block_size = block_size
        self.max_len = max_len
        self.max_seqs = max_seqs
        self.n_pages = -(-max_len // block_size)
        self.chunk_tokens = chunk_blocks * block_size
        self.chunk_burst = chunk_burst   # max prefill chunks per tick when
                                         # the batch is decode-starved
        # keep this fraction of the arena free of *cache-only* blocks: a
        # tree that grows to fill the arena makes every admission evict —
        # and LRU eviction under full-arena pressure throws away exactly
        # the chains the next FIFO request was about to hit (cache thrash)
        self.cache_watermark = cache_watermark
        self.preemption = preemption
        # the engine's admission policy also orders THIS instance's chunked-
        # prefill queue (None / is_fifo → admission-order, the old behavior)
        self.policy = policy
        self._fns = _paged_fns(ev)
        # sharded instances run the SAME jitted fns — computation follows
        # the committed params/arena, specializing per sharding layout
        self.params = (ev.params if mesh is None
                       else _sharded_params(ev, mesh))
        self.arena = R.make_block_arena(ev.cfg, n_blocks, block_size,
                                        dtype=jnp.float32, mesh=mesh)
        self.alloc = BlockAllocator(n_blocks, block_size)
        self.prefix: Optional[RadixPrefixCache] = (
            RadixPrefixCache(self.alloc) if prefix_caching else None)
        self.rows: List[Optional[_PagedSeq]] = [None] * max_seqs
        self.tables = np.zeros((max_seqs, self.n_pages), np.int32)
        self.lengths = np.zeros((max_seqs,), np.int32)
        self._next = np.zeros((max_seqs, 1), np.int32)
        self._prefillq: Deque[_PagedSeq] = deque()
        self._adm_seq = 0                # admission counter (policy tie-break)
        self.prefill_chunks = 0
        self.prefix_hit_tokens = 0
        self.preemptions = 0
        # disaggregation traffic (lifetime; session deltas): sequences this
        # worker staged out for a decode worker, and the pages that moved
        self.handoffs_out = 0
        self.handoff_pages = 0
        # swap-in page accounting: ``total`` counts the pages a FULL restore
        # would have written back, ``copied`` the pages actually written —
        # the gap is what the radix tree's surviving blocks saved
        self.swapin_pages_total = 0
        self.swapin_pages_copied = 0
        self._shapes: set = set()        # jit shape keys seen (see _note_shape)
        self.retraces = 0                # lifetime post-warmup shape misses
        # --- device-resident decode hot path ---------------------------------
        # ``pipeline=False`` is the synchronous reference loop: loop state is
        # re-uploaded every tick and every dispatch lands in its own tick —
        # the pre-pipelining behavior, kept as the greedy-parity oracle.
        self.pipeline = pipeline
        self.fused_steps = max(int(fused_steps), 1)
        # device mirrors of (next, tables, lengths, active): uploaded only
        # when an EVENT (admission, prefill completion, release, preemption,
        # table growth, compaction) dirties the host copies — steady-state
        # decode runs entirely on device
        self._dev: Optional[dict] = None
        self._dev_B = 0
        self._dev_active: Optional[np.ndarray] = None
        self._dirty = True
        self._inflight: Deque[_PendingDecode] = deque()
        self._pending_first: List[_PendingFirst] = []
        self._tick_id = 0
        # per-tick landing accumulators (reset at each tick() entry; the
        # flush helpers append here so _swap_out can force-land mid-tick)
        self._ev_emitted: List[Tuple[int, int]] = []
        self._ev_finished: List[_PagedSeq] = []
        self._ld_s = 0.0                 # landed decode seconds this tick
        self._ld_decodes: List[Tuple[float, float, int, List[int]]] = []
        # host↔device traffic + dispatch counters (lifetime; session deltas)
        self.host_syncs = 0
        self.h2d_transfers = 0
        self.decode_dispatches = 0

    # --- lifecycle -----------------------------------------------------------
    def reset(self) -> None:
        """Recycle from the warm pool: fresh allocator/prefix state; arena
        contents are stale but unreachable (no tables point at them)."""
        self.alloc = BlockAllocator(self.alloc.n_blocks, self.block_size)
        if self.prefix is not None:
            self.prefix = RadixPrefixCache(self.alloc)
        self.rows = [None] * self.max_seqs
        self.tables[:] = 0
        self.lengths[:] = 0
        self._next[:] = 0
        self._prefillq.clear()
        self._inflight.clear()
        self._pending_first.clear()
        self._dev = None
        self._dev_B = 0
        self._dev_active = None
        self._dirty = True

    def _put_rows(self, arr: np.ndarray):
        """Upload one (B, ...) loop-state buffer.  Under a mesh the leading
        row dim shards over "data" when divisible (replicated otherwise) so
        the decode batch splits across data-parallel devices; without a mesh
        this is a plain ``jnp.asarray`` (the PR 7 behavior, bit-identical)."""
        if self.mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec
        nd = self.mesh.shape.get("data", 1)
        ax = "data" if nd > 1 and arr.shape[0] % nd == 0 else None
        spec = PartitionSpec(ax, *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def warmup(self) -> None:
        """Compile every shape the serve loop can reach: the (single)
        fixed-size prefill chunk plus, per power-of-two row bucket
        (``_row_buckets`` — the batch-axis analogue of ``serve_buckets``),
        the fused decode at both step counts the loop dispatches (k = 1
        pipelined single-step, k = ``fused_steps`` when eligible), and the
        first-token argmax with its scatter into each bucket's ``next``.
        ``true_c = 0`` / an all-False mask route every warmup write to the
        junk block, so logical state is untouched."""
        dummy = jnp.zeros((1, self.chunk_tokens), jnp.int32)
        for span in self._page_buckets():
            self._shapes.add(("prefill_paged", span))
            lg, self.arena = self._fns["prefill_paged"](
                self.params, dummy, self.arena,
                jnp.zeros((span,), jnp.int32), 0, 0)
            lg.block_until_ready()
        tok = self._fns["first_token"](lg, 0)
        ks = sorted({1, self.fused_steps})
        for B in self._row_buckets():
            self._fns["set_next"](self._put_rows(self._next[:B]), 0,
                                  tok).block_until_ready()
            for k in ks:
                self._shapes.add(("decode_multi", B, k))
                toks, self.arena, _, _ = self._fns["decode_multi"](
                    self.params, self.arena, self._put_rows(self._next[:B]),
                    self._put_rows(self.tables[:B]),
                    self._put_rows(self.lengths[:B]),
                    self._put_rows(np.zeros((B,), bool)), k=k)
                toks.block_until_ready()

    # --- capacity ------------------------------------------------------------
    @property
    def occupied(self) -> int:
        return sum(1 for s in self.rows if s is not None)

    @property
    def capacity(self) -> int:
        return self.max_seqs

    @property
    def busy(self) -> bool:
        return self.occupied > 0

    def _avail_blocks(self) -> int:
        return self.alloc.num_free + (self.prefix.evictable_blocks()
                                      if self.prefix else 0)

    def admission_signature(self) -> Tuple:
        """Free-capacity fingerprint for admission gating: a failed
        block-aware admission is only re-attempted once the allocator state
        (free list OR any refcount — the prefix tree's evictable set is a
        pure function of refcounts) or a batch row changed.  The allocator
        ``version`` makes this O(1): re-peeking + re-walking the evictable
        set every tick when nothing was freed is pure waste."""
        return (sum(1 for s in self.rows if s is None), self.alloc.version)

    def can_admit(self, prompt_len: int, n_new: int) -> bool:
        """Admission control by BLOCK availability: a free batch row plus
        enough free-or-evictable blocks.  Without preemption the worst case
        (no prefix hit) of the WHOLE sequence is reserved; with preemption
        only the prompt needs to fit now — decode grows on demand and block
        pressure is resolved by swapping victims out."""
        assert prompt_len + n_new <= self.max_len, \
            f"prompt {prompt_len} + n_new {n_new} > max_len {self.max_len}"
        # a prefill worker never decodes here: only the prompt's blocks are
        # ever written before handoff, so that is all admission reserves
        reserve = (prompt_len if self.preemption or self.role == "prefill"
                   else prompt_len + n_new)
        need = self.alloc.blocks_for_tokens(reserve)
        assert need <= self.alloc.num_allocatable, \
            f"request needs {need} blocks > arena {self.alloc.num_allocatable}"
        if all(s is not None for s in self.rows):
            return False
        return self._avail_blocks() >= need

    # --- admission -----------------------------------------------------------
    def admit_next(self, rid: int, t_arrival: float, prompt: np.ndarray,
                   n_new: int, priority: int = 0, slo: str = "interactive",
                   deadline_s: Optional[float] = None
                   ) -> Tuple[_PagedSeq, float]:
        """Reserve blocks + a batch row; NO forward pass happens here —
        prefill is chunked across subsequent ticks (so admission never
        stalls sequences that are already decoding).  Shared prompt-prefix
        blocks come from the radix cache already prefilled."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        true_len = int(prompt.shape[0])
        row = self.rows.index(None)
        matched: List[int] = []
        n_cached = 0
        if self.prefix is not None:
            matched, n_cached = self.prefix.match(prompt)
        reserve = (true_len if self.preemption or self.role == "prefill"
                   else true_len + n_new)
        need = self.alloc.blocks_for_tokens(reserve) - len(matched)
        if need > self.alloc.num_free and self.prefix is not None:
            self.prefix.evict(need - self.alloc.num_free)
        blocks = matched + self.alloc.alloc(need)
        seq = _PagedSeq(rid, t_arrival, prompt, n_new, row, blocks,
                        n_done=n_cached, cached_tokens=n_cached,
                        remaining=n_new, priority=priority, slo=slo,
                        deadline_s=deadline_s, seq=self._adm_seq)
        self._adm_seq += 1
        self.tables[row, :len(blocks)] = blocks
        self.tables[row, len(blocks):] = 0
        self.lengths[row] = 0            # row inactive until prefill completes
        self._next[row, 0] = 0
        self.rows[row] = seq
        self._prefillq.append(seq)
        self.prefix_hit_tokens += n_cached
        self._dirty = True               # admission event: mirrors changed
        return seq, 0.0

    # --- preemption / swap ---------------------------------------------------
    def can_resume(self, swap: _SwapState) -> bool:
        """Re-admission check for a swapped-out sequence: a free row plus
        its saved block count (decode re-grows past that on demand)."""
        if all(s is not None for s in self.rows):
            return False
        return self._avail_blocks() >= swap.n_blocks

    def resume(self, swap: _SwapState) -> Tuple[_PagedSeq, float]:
        """Restore a preempted sequence — PARTIALLY when the radix tree
        still holds its prompt blocks.

        The leading pages recorded tree-backed at swap-out are re-acquired
        from the prefix cache (``match_full``: a reference per block, no
        device copy — their K/V never left the arena); only the evicted
        tail pages are written back from the host image.  If the tree
        dropped the nodes in the meantime the match comes back short and
        the difference is restored from host — bit-exact either way, so
        greedy decode continues on identical state."""
        row = self.rows.index(None)
        nb = swap.n_blocks
        reused: List[int] = []
        if self.prefix is not None and swap.tree_blocks > 0:
            reused = self.prefix.match_full(
                swap.prompt, max_blocks=min(swap.tree_blocks, nb))
        n_tail = nb - len(reused)
        if n_tail > self.alloc.num_free and self.prefix is not None:
            self.prefix.evict(n_tail - self.alloc.num_free)
        tail = self.alloc.alloc(n_tail)
        if n_tail:
            # jitted donated page scatter: the un-jitted ``.at[].set`` copied
            # the WHOLE arena per restore.  The tail count is padded to its
            # bucket (extra slots write zero pages into junk block 0, which
            # is garbage by contract) so restore compiles per bucket, not
            # per tail length.
            pb = _pow2_bucket(n_tail, self.n_pages)
            idx = np.zeros((pb,), np.int32)
            idx[:n_tail] = tail
            hk = swap.host_k[:, len(reused):]
            hv = swap.host_v[:, len(reused):]
            if pb != n_tail:
                pad = [(0, 0)] * hk.ndim
                pad[1] = (0, pb - n_tail)
                hk = np.pad(hk, pad)
                hv = np.pad(hv, pad)
            self.h2d_transfers += 3      # index vector + K + V page uploads
            with self.profiler.span("swap_h2d"):
                self.arena = self._fns["restore_paged"](
                    self.arena, jnp.asarray(idx), jnp.asarray(hk),
                    jnp.asarray(hv))
        blocks = reused + tail
        self.swapin_pages_total += nb
        self.swapin_pages_copied += n_tail
        seq = _PagedSeq(swap.rid, swap.t_arrival, swap.prompt, swap.n_new,
                        row, blocks, n_done=len(swap.prompt),
                        cached_tokens=swap.cached_tokens,
                        remaining=swap.remaining, tokens=list(swap.tokens),
                        t_prefilled=swap.t_prefilled, t_first=swap.t_first,
                        priority=swap.priority,
                        preempts=swap.preempts, slo=swap.slo,
                        deadline_s=swap.deadline_s, seq=self._adm_seq)
        self._adm_seq += 1
        self.tables[row, :nb] = blocks
        self.tables[row, nb:] = 0
        self.lengths[row] = swap.n_ctx
        self._next[row, 0] = swap.next_token
        self.rows[row] = seq
        self._dirty = True               # swap-in event: mirrors changed
        return seq, 0.0

    def _select_victim(self, exclude: _PagedSeq) -> Optional[_PagedSeq]:
        """Preemption victim: lowest priority first, youngest (latest
        arrival) within a level; only fully-prefilled decoding sequences
        qualify (mid-prefill rows sit in the prefill queue)."""
        cands = [s for s in self.rows
                 if s is not None and s.prefilled and s.remaining > 0
                 and s is not exclude]
        if not cands:
            return None
        return min(cands, key=lambda s: (s.priority, -s.t_arrival))

    def _swap_out(self, seq: _PagedSeq) -> _SwapState:
        """Swap a sequence's K/V pages to host memory and release its arena
        blocks + batch row.  The engine re-queues the returned image.

        ``tree_blocks`` snapshots how many leading pages the radix tree
        backs at this instant (full prompt blocks the cache still maps):
        those are the pages ``resume`` will try to re-acquire by reference
        instead of copying back.  The host image still saves every page —
        the snapshot is a ceiling, not a promise, because LRU eviction may
        drop the nodes before re-admission.

        The page copy is STAGED: a jitted (bucket-padded) device gather
        with ``copy_to_host_async`` started immediately, so the transfer
        overlaps the decode ticks between swap-out and resume instead of
        blocking the loop here.  Any in-flight decode work is landed first
        (the image must contain the sequence's true tokens/lengths)."""
        return self._stage_out(seq, count_preempt=True)

    def handoff_out(self, seq: _PagedSeq) -> _SwapState:
        """Stage a FULLY-PREFILLED sequence out for prefill→decode handoff
        (``serving.disagg``): same staged page gather + row/block release
        as a swap-out, but it is a planned transfer, not a preemption — the
        sequence's ``preempts`` count and this instance's ``preemptions``
        counter stay untouched; ``handoffs_out``/``handoff_pages`` record
        the traffic instead.  Only the sequence's own pending first token
        is landed (there are no in-flight decodes on a prefill worker), so
        extracting one handoff never force-flushes its neighbours."""
        assert seq.prefilled and seq.pending_first is None, \
            f"handoff of rid {seq.rid} before its first token landed"
        swap = self._stage_out(seq, count_preempt=False)
        self.handoffs_out += 1
        self.handoff_pages += swap.nb
        return swap

    def _stage_out(self, seq: _PagedSeq, *,
                   count_preempt: bool) -> _SwapState:
        if count_preempt:
            self._flush_all()            # pending tokens become part of image
        elif seq.pending_first is not None:
            self._land_first(seq.pending_first)
        n_ctx = int(self.lengths[seq.row])
        nb = self.alloc.blocks_for_tokens(max(n_ctx, 1))
        pb = _pow2_bucket(nb, self.n_pages)
        idx = np.zeros((pb,), np.int32)  # pad with junk pages: gathered then
        idx[:nb] = seq.blocks[:nb]       # sliced off at materialisation
        tree_blocks = 0
        if self.prefix is not None:
            tree_blocks = self.prefix.live_prefix_blocks(seq.prompt, limit=nb)
        with self.profiler.span("swap_d2h"):
            img_k, img_v = self._fns["gather_pages"](self.arena,
                                                     jnp.asarray(idx))
            img_k.copy_to_host_async()
            img_v.copy_to_host_async()
        swap = _SwapState(
            rid=seq.rid, t_arrival=seq.t_arrival, prompt=seq.prompt,
            n_new=seq.n_new, priority=seq.priority, tokens=list(seq.tokens),
            remaining=seq.remaining, n_ctx=n_ctx,
            next_token=int(self._next[seq.row, 0]), t_first=seq.t_first,
            t_prefilled=seq.t_prefilled, cached_tokens=seq.cached_tokens,
            preempts=seq.preempts + (1 if count_preempt else 0),
            img_k=img_k, img_v=img_v, nb=nb,
            tree_blocks=tree_blocks, slo=seq.slo, deadline_s=seq.deadline_s)
        self.alloc.free(seq.blocks)      # decref: prefix-tree refs survive
        self._clear_row(seq)
        if count_preempt:
            self.preemptions += 1
        return swap

    def _ensure_decode_capacity(self) -> List[_SwapState]:
        """Pre-decode pass under ``preemption=True``: grow every decoding
        row's block table to cover its next token write, swapping out
        victims when the arena (free list + evictable prefix blocks) runs
        dry.  Restarts after every mutation — ``_compact`` reshuffles rows,
        so cached indices would go stale."""
        swapped: List[_SwapState] = []
        while True:
            needy = None
            for i, s in enumerate(self.rows):
                if (s is not None and s.prefilled and s.remaining > 0
                        and self.alloc.blocks_for_tokens(
                            int(self.lengths[i]) + 1) > len(s.blocks)):
                    needy = s
                    break
            if needy is None:
                return swapped
            if self.alloc.num_free < 1 and self.prefix is not None:
                self.prefix.evict(1)
            if self.alloc.num_free >= 1:
                bid = self.alloc.alloc(1)[0]
                needy.blocks.append(bid)
                self.tables[needy.row, len(needy.blocks) - 1] = bid
                self._dirty = True       # table growth: mirrors changed
                continue
            if self._inflight or self._pending_first:
                # land in-flight work before choosing a victim: a pending
                # completion may release its blocks and spare the swap
                self._flush_all()
                continue
            victim = self._select_victim(exclude=needy) or needy
            swapped.append(self._swap_out(victim))

    def _release(self, seq: _PagedSeq) -> None:
        self.alloc.free(seq.blocks)      # decref: prefix-tree refs survive
        self._clear_row(seq)
        self._enforce_watermark()

    def _clear_row(self, seq: _PagedSeq) -> None:
        self.rows[seq.row] = None
        self.tables[seq.row, :] = 0
        self.lengths[seq.row] = 0
        self._next[seq.row, 0] = 0
        self._compact(seq.row)
        self._dirty = True               # release event: mirrors changed

    def _compact(self, hole: int) -> None:
        """Keep occupied rows a contiguous prefix: move the highest occupied
        row into the freed hole (host bookkeeping only — arena blocks never
        move).  Compactness is what lets ``tick`` decode over a power-of-two
        row bucket instead of all ``max_seqs`` static rows: a batch with 5
        live sequences pays for 8 rows of gather+compute, not 16."""
        last = max((i for i, s in enumerate(self.rows) if s is not None),
                   default=-1)
        if last <= hole:
            return
        seq = self.rows[last]
        self.rows[hole], self.rows[last] = seq, None
        seq.row = hole
        self.tables[hole] = self.tables[last]
        self.tables[last, :] = 0
        self.lengths[hole] = self.lengths[last]
        self.lengths[last] = 0
        self._next[hole, 0] = self._next[last, 0]
        self._next[last, 0] = 0

    def _row_buckets(self) -> List[int]:
        """Decode-batch buckets (batch-axis analogue of ``serve_buckets``):
        the ``_bucket_ladder`` over ``max_seqs``."""
        return _bucket_ladder(self.max_seqs)

    def _page_buckets(self) -> List[int]:
        """Prefill KV-span buckets: the ``_bucket_ladder`` over ``n_pages``.
        A chunk's queries can only see the first ``n_past + true_c``
        positions, so gathering/attending over the full table width wastes
        ~4× compute on the early chunks of a long prompt — the span is
        sliced to the smallest covering bucket."""
        return _bucket_ladder(self.n_pages)

    def _enforce_watermark(self) -> None:
        """Trim cache-only blocks until the free watermark holds, so the
        next admission draws from the free list instead of fighting the
        tree for whatever LRU eviction happens to surrender."""
        if self.prefix is None:
            return
        target = int(self.cache_watermark * self.alloc.num_allocatable)
        if self.alloc.num_free < target:
            self.prefix.evict(target - self.alloc.num_free)

    # --- serving -------------------------------------------------------------
    def _prefill_chunk(self, seq: _PagedSeq) -> None:
        """Advance one chunk of ``seq``'s prompt through the arena.  The
        final chunk's last-position logits yield the first generated token
        (never discarded), and the prompt's full blocks register in the
        prefix tree for future sharing.

        The first token STAYS ON DEVICE: its argmax is dispatched (with an
        async host copy) instead of the old blocking ``int(jnp.argmax(...))``
        per final chunk, and the pending device scalar is scattered into the
        ``next`` buffer at the following upload — the host records its value
        through the pipelined landing path (``_land_first``), which stamps
        ``t_first``; the final chunk's dispatch stamps ``t_prefilled``."""
        start = seq.n_done
        true_c = min(self.chunk_tokens, len(seq.prompt) - start)
        padded = np.zeros((1, self.chunk_tokens), np.int32)
        padded[0, :true_c] = seq.prompt[start:start + true_c]
        # slice the visible KV span to its page bucket: this chunk's queries
        # end at start + true_c, so later pages are causally invisible
        span = _pow2_bucket(-(-(start + true_c) // self.block_size),
                            self.n_pages)
        _note_shape(self, ("prefill_paged", span))
        self.h2d_transfers += 2          # padded chunk + table-slice uploads
        logits, self.arena = self._fns["prefill_paged"](
            self.params, jnp.asarray(padded), self.arena,
            jnp.asarray(self.tables[seq.row][:span]), start, true_c)
        seq.n_done += true_c
        self.prefill_chunks += 1
        if seq.prefilled:
            tok = self._fns["first_token"](logits, true_c - 1)
            tok.copy_to_host_async()
            seq.t_prefilled = time.perf_counter()
            pf = _PendingFirst(seq, tok, self._tick_id)
            self._pending_first.append(pf)
            seq.pending_first = pf
            seq.remaining -= 1
            self.lengths[seq.row] = len(seq.prompt)
            self._dirty = True           # row activation: mirrors changed
            if self.prefix is not None:
                self.prefix.insert(seq.prompt, seq.blocks)

    # --- pipelined landing ----------------------------------------------------
    def _land_first(self, pf: _PendingFirst) -> None:
        """Record a pending first token on the host and stamp ``t_first``:
        the moment its value is known here.  Landing in the tick that
        created it is a forced (blocking) round-trip and counts as a
        ``host_syncs``; landing later overlapped host work for free."""
        seq = pf.seq
        sync = pf.tick_id == self._tick_id
        if sync:
            self.host_syncs += 1
        with self.profiler.span("decode_land", sync=sync):
            first = int(np.asarray(pf.tok))
            seq.t_first = time.perf_counter()
            seq.tokens.append(first)     # tokens[0]: decode landings wait
            self._ev_emitted.append((seq.rid, first))
            seq.pending_first = None
            if pf in self._pending_first:
                self._pending_first.remove(pf)
            if self.rows[seq.row] is seq:
                self._next[seq.row, 0] = first

    def _land_item(self, item: _PendingDecode) -> None:
        """Land one dispatched decode call: block on its (k, B) token
        readback, append tokens in dispatch order, advance the landing
        accumulators, and complete sequences whose final tokens arrived.
        A sequence's pending first token (if any) lands first — per-request
        token order is part of the greedy-parity contract."""
        sync = item.tick_id == self._tick_id
        if sync:
            self.host_syncs += 1         # same-tick landing: no overlap
        with self.profiler.span("decode_land", sync=sync) as sp:
            toks = np.asarray(item.toks)  # blocks until the async copy lands
        self._ld_s += item.dispatch_s + sp.seconds
        self._ld_decodes.append((item.t_dispatch, sp.t1, item.k,
                                 [s.rid for s, _ in item.rows]))
        done: List[_PagedSeq] = []
        for s, col in item.rows:
            if s.pending_first is not None:
                self._land_first(s.pending_first)
            for i in range(item.k):
                t = int(toks[i, col])
                s.tokens.append(t)
                self._ev_emitted.append((s.rid, t))
            s.pending_steps -= item.k
            self._next[s.row, 0] = int(toks[item.k - 1, col])
            if s.remaining <= 0 and s.pending_steps <= 0:
                done.append(s)
        for s in done:                   # release AFTER the sweep: _compact
            self._ev_finished.append(s)  # moves rows and would skew columns
            self._release(s)

    def _land_ready(self) -> None:
        """Collect readbacks dispatched BEFORE this tick — their async
        copies overlapped at least one full tick of host work, so these
        landings are free (no ``host_syncs``).  Decode calls land first:
        the tick emits nothing to clients before its last landing, so a
        first token read ahead of them would be stamped (``t_first``)
        before the wait that still holds it back from its client."""
        while self._inflight and self._inflight[0].tick_id < self._tick_id:
            self._land_item(self._inflight.popleft())
        for pf in list(self._pending_first):
            if pf.tick_id < self._tick_id:
                self._land_first(pf)

    def _flush_decodes(self) -> None:
        """Force-land every in-flight decode call (upload precondition:
        host mirrors must equal device state).  Pending FIRST tokens stay
        pending — the upload scatters their device scalars into ``next``
        instead of blocking on them."""
        while self._inflight:
            self._land_item(self._inflight.popleft())

    def _flush_all(self) -> None:
        """Force-land everything, first tokens included — the swap-out /
        victim-selection path, where the host image must carry the true
        tokens, lengths, and next-token of every sequence."""
        self._flush_decodes()
        for pf in list(self._pending_first):
            self._land_first(pf)

    def _upload(self, B: int, active: np.ndarray) -> None:
        """Push fresh loop state (next, tables, lengths, active) for row
        bucket ``B`` — the EVENT path.  Unlanded first tokens are scattered
        into the uploaded ``next`` buffer as device scalars, so a prefill
        completion never blocks the loop on its own argmax."""
        assert not self._inflight, "upload with stale in-flight decodes"
        nxt = self._put_rows(self._next[:B])
        for pf in self._pending_first:
            nxt = self._fns["set_next"](nxt, pf.seq.row, pf.tok)
        self._dev = {"next": nxt,
                     "tables": self._put_rows(self.tables[:B]),
                     "lengths": self._put_rows(self.lengths[:B]),
                     "active": self._put_rows(active[:B])}
        self.h2d_transfers += 4
        self._dev_B = B
        self._dev_active = active[:B].copy()
        self._dirty = False

    def _choose_k(self, act_rows: List[_PagedSeq]) -> int:
        """Fused-dispatch step count: ``fused_steps`` when every active row
        can absorb k on-device steps with zero host intervention — no
        prefill work queued, ``remaining >= k`` (no completion inside the
        window), and block-table headroom for k more tokens (no growth or
        swap inside the window) — else 1.  Fusion is bit-identical to k
        single steps (`decode_paged_multi`), so eligibility only shapes
        dispatch granularity, never tokens."""
        k = self.fused_steps
        if k <= 1 or not self.pipeline or self._prefillq:
            return 1
        bs = self.block_size
        for s in act_rows:
            if (s.remaining < k
                    or len(s.blocks) * bs < int(self.lengths[s.row]) + k):
                return 1
        return k

    def _decodable(self) -> int:
        return sum(1 for s in self.rows
                   if s is not None and s.prefilled and s.remaining > 0)

    def _next_prefill(self, now: Optional[float]) -> int:
        """Index into ``_prefillq`` of the next chunk to advance, delegated
        to the engine's admission policy: under ``priority``/``edf``/the
        carbon policies an interactive admission's chunks preempt a long
        background prefill *mid-prompt* inside the same burst budget,
        instead of queueing behind it in admission order.  FIFO (or no
        policy) keeps the original head-first behavior; a policy hold falls
        back to the head too (``select_prefill`` — admitted work holds
        blocks, parking it only strands memory)."""
        if (self.policy is None or getattr(self.policy, "is_fifo", False)
                or len(self._prefillq) == 1):
            return 0
        return self.policy.select_prefill(list(self._prefillq), now)

    def tick(self, now: Optional[float] = None, allow_fused: bool = True
             ) -> Tuple[List[_PagedSeq], Dict[str, object]]:
        """One scheduler tick of the PIPELINED decode loop: an adaptive
        prefill budget, then one batched decode DISPATCH over all decoding
        rows (fused to ``fused_steps`` device-fed steps when eligible),
        then the LANDING of whatever readbacks finished overlapping earlier
        ticks.  ``now`` is the engine's session-relative clock, passed
        through to the policy ordering the prefill queue.

        Steady state touches the host ZERO times per tick: loop state lives
        on device (``_dev``), the greedy token feeds back inside the jitted
        call, and tick N's (k, B) token block lands while tick N+1's
        dispatch is already queued.  Only EVENTS (admission, prefill
        completion, release, growth, preemption) dirty the mirrors and
        trigger a flush + one re-upload.  The tick info therefore describes
        LANDED decode work — possibly dispatched an earlier tick — while
        the prefill fields stay dispatch-accounted.

        Prefill policy: while the batch is decode-starved (fewer decodable
        rows than half the row capacity), burst up to ``chunk_burst``
        policy-ordered chunks — stalling nobody, since there is little to
        stall — and back off to a SINGLE chunk per tick once decode
        concurrency is healthy, so a 512-token admission interleaves with
        running decodes instead of pausing them for its whole prefill."""
        self._tick_id += 1
        self._ev_emitted = []
        self._ev_finished = []
        self._ld_s = 0.0
        self._ld_decodes = []
        prefill_rids: List[Tuple[int, float, float]] = []
        prefill_s = 0.0
        if self._prefillq:
            burst = 0
            while self._prefillq:
                if burst >= self.chunk_burst:
                    break
                if burst > 0 and self._decodable() >= max(
                        1, min(self.occupied, self.max_seqs // 2)):
                    break                        # decode is busy: yield
                qi = self._next_prefill(now)
                seq = self._prefillq[qi]
                with self.profiler.span("prefill_chunk", rid=seq.rid) as sp:
                    self._prefill_chunk(seq)
                prefill_rids.append((seq.rid, sp.t0, sp.t1))
                prefill_s += sp.seconds
                burst += 1
                if seq.prefilled:
                    del self._prefillq[qi]
                    if seq.remaining <= 0:       # n_new == 1: the request IS
                        self._land_first(seq.pending_first)  # its first token
                        self._ev_finished.append(seq)
                        self._release(seq)
        if self.role == "prefill":
            # a prefill worker never dispatches decode: land first-token
            # readbacks whose async copies overlapped an earlier tick (the
            # disagg layer extracts those sequences via handoff_out), skip
            # table growth / decode dispatch / preemption entirely
            self._land_ready()
            return self._ev_finished, _tick_info(
                prefill_s=prefill_s,
                blocks_in_use=self.alloc.blocks_in_use(),
                prefill_rids=prefill_rids, emitted=self._ev_emitted)
        # decode-time block pressure: grow tables on demand, swap victims
        # out when the arena is dry (PREEMPTED lifecycle state)
        preempted = self._ensure_decode_capacity() if self.preemption else []
        active = np.array([s is not None and s.prefilled and s.remaining > 0
                           for s in self.rows])
        occ = int(active.sum())
        B = self._dev_B
        if occ:
            # occupied rows are a compact prefix (see _compact): decode over
            # the smallest power-of-two row bucket covering them, so 5 live
            # sequences cost 8 rows of gather+compute, not max_seqs
            B = _pow2_bucket(self.occupied, self.max_seqs)
            if (self._dirty or self._dev is None or B != self._dev_B
                    or not self.pipeline
                    or not np.array_equal(active[:B], self._dev_active)):
                # EVENT path: land in-flight work (mirrors must equal the
                # device state), then push fresh loop state once
                with self.profiler.span("upload"):
                    self._flush_decodes()    # may release rows -> recompute
                    active = np.array([s is not None and s.prefilled
                                       and s.remaining > 0
                                       for s in self.rows])
                    occ = int(active.sum())
                    if occ:
                        B = _pow2_bucket(self.occupied, self.max_seqs)
                        self._upload(B, active)
        if occ:
            act_rows = [s for s in self.rows[:B]
                        if s is not None and s.prefilled and s.remaining > 0]
            k = self._choose_k(act_rows) if allow_fused else 1
            _note_shape(self, ("decode_multi", B, k))
            with self.profiler.span("decode_dispatch", k=k, rows=occ) as sp:
                toks, self.arena, nxt, ln = self._fns["decode_multi"](
                    self.params, self.arena, self._dev["next"],
                    self._dev["tables"], self._dev["lengths"],
                    self._dev["active"], k=k)
                self._dev["next"], self._dev["lengths"] = nxt, ln
                toks.copy_to_host_async()
            self.decode_dispatches += 1
            for s in act_rows:           # predictive mirrors: decremented at
                s.remaining -= k         # dispatch; truth lands later
                s.pending_steps += k
                self.lengths[s.row] += k
            self._inflight.append(_PendingDecode(
                toks, [(s, s.row) for s in act_rows], k, sp.t0, sp.seconds,
                self._tick_id))
        # LANDING: readbacks dispatched before this tick overlapped a full
        # tick of host work — collect them for free; the synchronous
        # reference mode (pipeline=False) lands everything immediately
        if self.pipeline:
            self._land_ready()
        else:
            self._flush_all()
        return self._ev_finished, _tick_info(
            prefill_s=prefill_s, decode_s=self._ld_s,
            blocks_in_use=self.alloc.blocks_in_use(),
            prefill_rids=prefill_rids, decodes=self._ld_decodes,
            emitted=self._ev_emitted,
            preempted=preempted)


# =============================================================================
# engine
# =============================================================================
class _Session:
    """One serve session's bookkeeping: the policy queue, the open-loop
    release schedule, per-request energy meters, swapped-out images, the
    admission gate, and the aggregate counters ``stats`` reports."""

    def __init__(self, core: SchedulerCore, instances,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[TraceRecorder] = None) -> None:
        self.core = core
        self.registry = (registry if registry is not None
                         else MetricsRegistry.standard("real"))
        self.tracer = tracer
        self.span_ids: Dict[int, int] = {}       # rid → "request" span sid
        self.preempt_sids: Dict[int, int] = {}   # rid → open "preempted" sid
        self.t0 = time.perf_counter()
        self.future: List[Tuple[float, int, int]] = []   # (t_abs, seq, rid)
        self._fseq = 0
        self.requests: Dict[int, InferenceRequest] = {}
        self.meters: Dict[int, float] = {}
        self.swapped: Dict[int, _SwapState] = {}
        self.variant_of: Dict[int, str] = {}     # rid → decided ladder rung
        self.admit_gate: Dict[int, Tuple] = {}           # id(inst) → (rid, sig)
        self.admit_t: Dict[int, float] = {}
        self.responses: List[InferenceResponse] = []
        self.admit_order: List[int] = []
        self.queue_delays: List[float] = []
        self.ttfts: List[float] = []
        self.prefill_waits: List[float] = []
        self.first_lags: List[float] = []
        self.energy = 0.0
        # per-role joules (disaggregation accounting): every charge is
        # tagged with the instance's role — "both" for monolithic engines,
        # "prefill"/"decode"/"handoff" under serving.disagg.  ``charge`` +
        # ``meter`` keep the ``energy``/``meters`` accumulation order
        # IDENTICAL to the untagged path, so monolithic numbers are
        # bit-for-bit unchanged and role sums conserve by construction.
        self.role_energy: Dict[str, float] = {}
        self.meters_role: Dict[int, Dict[str, float]] = {}
        self.handoffs = 0
        self.handoff_pages = 0
        self.decode_steps = 0
        self.occ_frac_sum = 0.0
        self.inflight_sum = 0
        self.admitted_sum = 0
        self.tick_samples = 0
        self.blocks_peak = 0
        self.preempt_total = 0
        self.progressed = False
        # wall seconds already charged per instance (prefill + decode); the
        # remainder of the serve wall is charged at idle power at drain, so
        # an allocated-but-idle instance is never free (same convention as
        # the DES's idle_chip_s accounting)
        self.accounted_s = {id(i): 0.0 for i in instances}
        # instance counters are lifetime (they survive reset/warm reuse);
        # stats report THIS session's delta
        self.chunks0 = sum(getattr(i, "prefill_chunks", 0) for i in instances)
        self.hits0 = sum(getattr(i, "prefix_hit_tokens", 0)
                         for i in instances)
        self.swap_total0 = sum(getattr(i, "swapin_pages_total", 0)
                               for i in instances)
        self.swap_copied0 = sum(getattr(i, "swapin_pages_copied", 0)
                                for i in instances)
        self.retraces0 = sum(getattr(i, "retraces", 0) for i in instances)
        self.syncs0 = sum(getattr(i, "host_syncs", 0) for i in instances)
        self.h2d0 = sum(getattr(i, "h2d_transfers", 0) for i in instances)
        self.dispatches0 = sum(getattr(i, "decode_dispatches", 0)
                               for i in instances)

    def charge(self, role: str, joules: float) -> None:
        """Add session energy under a role tag (see ``role_energy``)."""
        self.energy += joules
        self.role_energy[role] = self.role_energy.get(role, 0.0) + joules

    def meter(self, rid: int, role: str, joules: float) -> None:
        """Add per-request energy under a role tag (see ``meters_role``)."""
        self.meters[rid] += joules
        mr = self.meters_role.setdefault(rid, {})
        mr[role] = mr.get(role, 0.0) + joules

    def schedule(self, req: InferenceRequest) -> None:
        if req.arrival_s is None:
            self.core.submit(req.rid, self.t0, priority=req.priority,
                             deadline_s=req.deadline_s, slo=req.slo)
        else:
            heapq.heappush(self.future,
                           (self.t0 + float(req.arrival_s), self._fseq,
                            req.rid))
            self._fseq += 1

    def rel(self, now: float) -> float:
        """Session-relative seconds — the clock policies see.  Deadlines
        stay as submitted (relative to session start), so the SAME policy
        object behaves identically here and on the DES's simulated clock."""
        return now - self.t0


class RealEngine:
    """Maps a ConfigGraph onto real instances and serves
    :class:`InferenceRequest`s with continuous batching through the
    ``ServingBackend`` protocol, measuring wall latencies and attributing
    occupancy-scaled energy (the calibrated stand-in for TPU telemetry) and
    carbon (``ci_g_per_kwh``) per request.

    ``mesh=`` shards every paged instance across a ("data", "model") device
    mesh (``launch.mesh.make_mesh_for``); ``roles=`` splits the engine into
    prefill and decode workers — constructing ``RealEngine(..., roles=...)``
    transparently builds a :class:`serving.disagg.DisaggEngine` (same
    ``ServingBackend`` surface, so callers and the fleet's ``probe_window``
    drive it unchanged)."""

    def __new__(cls, *args, **kwargs):
        if cls is RealEngine and kwargs.get("roles"):
            from repro.serving.disagg import DisaggEngine
            return super().__new__(DisaggEngine)
        return super().__new__(cls)

    def __init__(self, family: Sequence[EngineVariant], n_slots: int = 4,
                 max_len: int = 96, *, kv_layout: str = "slotted",
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 max_seqs: Optional[int] = None, chunk_blocks: int = 2,
                 prefix_caching: bool = True,
                 policy: Union[str, SchedulerPolicy, None] = "fifo",
                 preemption: bool = False, ci_g_per_kwh: float = 0.0,
                 telemetry: Optional[Telemetry] = None,
                 decode_pipeline: bool = True, fused_steps: int = 8,
                 quality_selector=None, mesh=None, roles=None):
        assert kv_layout in ("slotted", "paged"), kv_layout
        assert not (preemption and kv_layout == "slotted"), \
            "preemption requires the paged KV layout (slots never grow)"
        assert mesh is None or kv_layout == "paged", \
            "mesh sharding requires the paged KV layout"
        assert not roles, \
            "roles= is the DisaggEngine's (serving.disagg) — RealEngine " \
            "dispatches there via __new__; do not pass roles to a subclass"
        self.mesh = mesh
        self.family = {ev.variant.name: ev for ev in family}
        self.instances: List[Instance] = []
        self.n_slots = n_slots
        self.max_len = max_len
        self.kv_layout = kv_layout
        self.block_size = block_size
        # equal-arena default: the paged pool holds exactly the KV tokens the
        # slotted cache would reserve (n_slots × max_len), plus the junk block
        self.n_blocks = (n_blocks if n_blocks is not None
                         else -(-n_slots * max_len // block_size) + 1)
        self.max_seqs = max_seqs if max_seqs is not None else 4 * n_slots
        self.chunk_blocks = chunk_blocks
        self.prefix_caching = prefix_caching
        self.policy = make_policy(policy)
        # mixed-quality request path: the selector decides each request's
        # ladder rung at submit; admission then only places it on instances
        # of that variant (serving.quality — name, instance, or None)
        self.quality_selector = make_selector(quality_selector)
        self.preemption = preemption
        # decode hot path: ``decode_pipeline=False`` selects the synchronous
        # reference loop (re-upload + blocking readback every tick) — the
        # greedy-parity oracle; ``fused_steps`` bounds on-device step fusion
        self.decode_pipeline = decode_pipeline
        self.fused_steps = fused_steps
        self.ci_g_per_kwh = ci_g_per_kwh
        # optional unified-telemetry bundle: the engine repoints its
        # ``registry`` at every session open (per-session registries) and
        # emits lifecycle spans into its persistent ``tracer``; its ``feed``
        # receives one exact (wall, joules, grams) segment per session
        self.telemetry = telemetry
        # one engine-owned phase profiler shared by every instance: its
        # spans annotate the device profiler's timeline (nothing is
        # recorded while no profiler session runs); its registry is
        # repointed at each session open (and set to None when no telemetry
        # bundle is attached, so no histogram sample is taken)
        self.profiler = PhaseProfiler(annotate=jax.profiler.TraceAnnotation)
        self.last_registry: Optional[MetricsRegistry] = None
        self._feed_clock = 0.0           # feed-time seconds across sessions
        self._pool: Dict[Tuple[str, int], List[Instance]] = {}
        self._session: Optional[_Session] = None
        self._last_stats: Dict[str, float] = {}
        self.last_reconfig_s = 0.0
        self.last_admit_order: List[int] = []
        self.last_outputs: Dict[int, np.ndarray] = {}
        self.last_latencies: List[float] = []
        self.last_responses: List[InferenceResponse] = []

    def _new_instance(self, ev: EngineVariant, chips: int,
                      role: str = "both"):
        if self.kv_layout == "paged":
            return PagedInstance(ev, chips, n_blocks=self.n_blocks,
                                 block_size=self.block_size,
                                 max_seqs=self.max_seqs,
                                 max_len=self.max_len,
                                 chunk_blocks=self.chunk_blocks,
                                 prefix_caching=self.prefix_caching,
                                 preemption=self.preemption,
                                 policy=self.policy,
                                 pipeline=self.decode_pipeline,
                                 fused_steps=self.fused_steps,
                                 mesh=self.mesh, role=role)
        return Instance(ev, chips, self.n_slots, self.max_len)

    def configure(self, graph) -> float:
        """Apply a configuration graph; returns reconfig seconds (measured).

        Warm path: instances are returned to a (variant, chips) pool and
        reused — weights, KV arenas and compiled functions survive
        controller re-invocations; only genuinely new (variant, chips) pairs
        pay allocation + compile."""
        assert self._session is None, "configure during an open serve session"
        t0 = time.perf_counter()
        for inst in self.instances:
            self._pool.setdefault((inst.ev.variant.name, inst.chips),
                                  []).append(inst)
        self.instances = []
        for (vname, chips), w in graph.edges:
            for _ in range(w):
                warm = self._pool.get((vname, chips), [])
                if warm:
                    inst = warm.pop()
                    inst.reset()
                else:
                    inst = self._new_instance(self.family[vname], chips)
                    inst.warmup()
                inst.profiler = self.profiler
                self.instances.append(inst)
        self.last_reconfig_s = time.perf_counter() - t0
        return self.last_reconfig_s

    # --- disaggregation hooks (overridden by serving.disagg) -----------------
    def _profilers(self):
        """Every phase profiler the engine repoints per session."""
        return (self.profiler,)

    def _takes(self, inst, resuming: bool) -> bool:
        """Whether ``inst`` participates in admitting the queue head (the
        DisaggEngine routes fresh work to prefill workers and swapped-out
        images to decode workers; monolithic instances take everything)."""
        return True

    def _post_tick(self, completed: List[InferenceResponse]) -> None:
        """End-of-step hook: the DisaggEngine extracts finished prefills
        into ``BlockHandoff``s and places them on decode workers here."""

    def _extra_pending(self) -> bool:
        """Work the drain loop must wait on beyond queues and busy
        instances (the DisaggEngine's in-transit handoff queue)."""
        return False

    # --- ServingBackend protocol ---------------------------------------------
    def submit(self, req: InferenceRequest) -> None:
        """Enqueue a typed request.  The first submit after idle opens a
        session (t0 = now); ``arrival_s`` schedules an open-loop release
        relative to it."""
        assert self.instances, "configure() first"
        if self._session is None:
            reg = MetricsRegistry.standard(f"real-{self.kv_layout}",
                                           labels={"kv_layout":
                                                   self.kv_layout})
            tel = self.telemetry
            if tel is not None:
                tel.registry = reg       # per-session registry (see obs)
            # phase profiling rides the telemetry opt-in: without a bundle
            # the profiler stays disabled and the hot path pays nothing
            for prof in self._profilers():
                prof.registry = reg if tel is not None else None
            self.policy.reset_holds()    # rids repeat across sessions
            self._session = _Session(
                SchedulerCore(self.policy), self.instances, registry=reg,
                tracer=tel.tracer if tel is not None else None)
            self.last_registry = reg
            self.last_admit_order = []
            self.last_outputs = {}
            if self.quality_selector is not None:
                # bind the selector to the rungs this configuration can
                # actually serve (deduped by name, any instance count)
                ladder = {inst.ev.variant.name: inst.ev.variant
                          for inst in self.instances}
                self.quality_selector.reset(list(ladder.values()))
        s = self._session
        assert req.rid not in s.requests, f"duplicate rid {req.rid}"
        s.requests[req.rid] = req
        s.meters[req.rid] = 0.0
        if self.quality_selector is not None:
            dec = self.quality_selector.select(req)
            s.variant_of[req.rid] = dec.variant
        s.registry.counter("requests_submitted").inc()
        s.schedule(req)

    def step(self) -> List[InferenceResponse]:
        """One scheduler pass: release due arrivals, run policy admission
        over every instance (gated re-attempts), then one tick (≤ one
        prefill chunk burst + one batched decode step) per busy instance.
        Returns the requests that completed on this pass."""
        s = self._session
        if s is None:
            return []
        with self.profiler.span("step"):
            s.progressed = False
            completed: List[InferenceResponse] = []
            with self.profiler.span("admit"):
                self._admit(s, completed)
            for inst in self.instances:
                if inst.busy:
                    with inst.profiler.span("tick"):
                        self._tick(s, inst, completed)
            self._post_tick(completed)
        return completed

    def _admit(self, s: _Session, completed: List[InferenceResponse]
               ) -> None:
        """Release due arrivals, then admission: peek the policy's next
        choice and place it on the first instance with capacity (slots or
        blocks) — mid-flight, so rows/blocks freed by the previous tick's
        completions refill.  A failed fit is GATED per instance: no
        re-attempt until the queue head or the instance's free capacity
        actually changes."""
        now = time.perf_counter()
        now_rel = s.rel(now)
        while s.future and s.future[0][0] <= now:
            t_arr, _, rid = heapq.heappop(s.future)
            req = s.requests[rid]
            s.core.submit(rid, t_arr, priority=req.priority,
                          deadline_s=req.deadline_s, slo=req.slo)
        for inst in self.instances:
            while True:
                nxt = s.core.peek_next(now_rel)
                if nxt is None:
                    break
                rid, t_arr = nxt
                # mixed-quality routing: the queue head only admits onto
                # instances of its decided rung (head-of-line blocking on a
                # variant-busy head is deliberate — identical on the DES).
                # Also keeps preempted swap images on their own variant.
                want = s.variant_of.get(rid)
                if want is not None and inst.ev.variant.name != want:
                    break
                # role routing (disagg): fresh work → prefill workers,
                # swapped/handed-off images → decode workers
                if not self._takes(inst, rid in s.swapped):
                    break
                sig = inst.admission_signature()
                if s.admit_gate.get(id(inst)) == (rid, sig):
                    break                # nothing changed since last failure
                req = s.requests[rid]
                swap = s.swapped.get(rid)
                fits = (inst.can_resume(swap) if swap is not None
                        else inst.can_admit(req.prompt_len,
                                            req.max_new_tokens))
                if not fits:
                    s.admit_gate[id(inst)] = (rid, sig)
                    break
                s.admit_gate.pop(id(inst), None)
                s.core.pop_next(now_rel)
                t1 = time.perf_counter()
                if swap is not None:
                    state, dt = inst.resume(swap)
                    del s.swapped[rid]
                    if s.tracer is not None:
                        t_res = s.rel(time.perf_counter())
                        sid = s.preempt_sids.pop(rid, None)
                        if sid is not None:
                            s.tracer.close_span(sid, t_res,
                                                pages=swap.n_blocks)
                        s.tracer.instant("swap_in", t_res, rid=rid)
                else:
                    state, dt = inst.admit_next(rid, t_arr, req.prompt,
                                                req.max_new_tokens,
                                                priority=req.priority,
                                                slo=req.slo,
                                                deadline_s=req.deadline_s)
                    s.admit_t[rid] = t1
                    s.queue_delays.append(t1 - t_arr)
                    s.admit_order.append(rid)
                    self.last_admit_order.append(rid)
                    if state.tokens and req.on_token is not None:
                        req.on_token(rid, state.tokens[0])   # slotted first
                    if s.tracer is not None:
                        s.tracer.instant("admit", s.rel(t1), rid=rid)
                e_pf = inst.chips * PM.P_BUSY_W * dt   # prefill: busy power
                s.charge(inst.role, e_pf)
                s.meter(rid, inst.role, e_pf)
                s.accounted_s[id(inst)] += dt
                s.progressed = True
                if state.remaining <= 0 and state.tokens:    # n_new == 1
                    completed.append(self._finish(state, inst))

    def _tick(self, s: _Session, inst,
              completed: List[InferenceResponse]) -> None:
        """One tick of a busy instance (≤ 1 prefill burst + 1 decode), then
        its accounting, lifecycle trace, token callbacks, preemptions and
        completions."""
        prof = inst.profiler
        s.progressed = True
        s.admitted_sum += inst.occupied   # holding cache memory now
        s.tick_samples += 1
        # fused multi-step dispatch stays off while timed arrivals are
        # outstanding: an open-loop session measures admission latency, and
        # a k-step device window would delay a mid-window arrival's prefill
        # behind k queued decode steps
        done, info = inst.tick(s.rel(time.perf_counter()),
                               allow_fused=not s.future)
        s.charge(inst.role, inst.chips * PM.P_BUSY_W * info["prefill_s"])
        for rid, t0, t1 in info["prefill_rids"]:
            s.meter(rid, inst.role, inst.chips * PM.P_BUSY_W * (t1 - t0))
        decodes = info["decodes"]
        ksteps = sum(k for _, _, k, _ in decodes)
        occ = max((len(rids) for _, _, _, rids in decodes), default=0)
        if ksteps:
            # info describes LANDED decode work: ``ksteps`` model steps
            # (>= 1 per landed dispatch, k per fused dispatch) sharing
            # ``decode_s`` wall seconds — aggregates stay step-weighted so
            # occupancy/inflight means are comparable across fused and
            # single-step sessions
            e_dec = PM.instance_power_w(
                inst.chips, occ / inst.capacity) * info["decode_s"]
            s.charge(inst.role, e_dec)
            rows = [rid for _, _, _, rids in decodes for rid in rids]
            share = e_dec / max(len(rows), 1)
            for rid in rows:
                s.meter(rid, inst.role, share)
            s.decode_steps += ksteps
            s.occ_frac_sum += (occ / inst.capacity) * ksteps
            s.inflight_sum += occ * ksteps
        s.accounted_s[id(inst)] += info["prefill_s"] + info["decode_s"]
        s.blocks_peak = max(s.blocks_peak, int(info["blocks_in_use"]))
        s.registry.gauge("occupied_rows").set(occ)
        s.registry.gauge("blocks_in_use").set(info["blocks_in_use"])
        if s.tracer is not None:
            tr = s.tracer
            # the lifecycle spans take the phase spans' own host stamps: a
            # chunk from its prep to its dispatch, a decode from its launch
            # to its landing (one span per landed dispatch, carrying its k)
            for rid, t0, t1 in info["prefill_rids"]:
                tr.span("prefill_chunk", s.rel(t0), s.rel(t1), rid=rid)
            for t0, t1, k, rids in decodes:
                tr.span("decode", s.rel(t0), s.rel(t1), k=k, rids=rids,
                        n=len(rids))
            # memory/power pressure counter tracks on the engine track
            # (Perfetto renders them alongside the request spans): the
            # arena/slot occupancy plus this tick's instantaneous power
            # draw under the same model that charges the energy
            if ksteps:
                p_w = PM.instance_power_w(inst.chips, occ / inst.capacity)
            elif info["prefill_s"] > 0:
                p_w = inst.chips * PM.P_BUSY_W
            else:
                p_w = inst.chips * PM.P_IDLE_W
            t_end = s.rel(time.perf_counter())
            tr.counter("blocks_in_use", t_end, info["blocks_in_use"])
            tr.counter("occupied_rows", t_end, occ)
            tr.counter("power_w", t_end, p_w)
        if info["emitted"]:
            with prof.span("emit"):
                for rid, tok in info["emitted"]:
                    cb = s.requests[rid].on_token
                    if cb is not None:
                        cb(rid, tok)
        for swap in info["preempted"]:
            req = s.requests[swap.rid]
            s.swapped[swap.rid] = swap
            s.preempt_total += 1
            if s.tracer is not None:
                t_sw = s.rel(time.perf_counter())
                s.tracer.instant("swap_out", t_sw, rid=swap.rid,
                                 pages=swap.n_blocks)
                s.preempt_sids[swap.rid] = s.tracer.open_span(
                    "preempted", t_sw, rid=swap.rid)
            s.core.requeue_front(swap.rid, swap.t_arrival,
                                 priority=req.priority,
                                 deadline_s=req.deadline_s,
                                 slo=req.slo)
        if done:
            with prof.span("finish"):
                for state in done:
                    completed.append(self._finish(state, inst))

    def drain(self) -> List[InferenceResponse]:
        """Run the session to completion; returns every response.  Closes
        the session: the idle-power floor is spread across the responses,
        carbon is attributed at ``ci_g_per_kwh``, and ``stats()`` reports
        the aggregates."""
        s = self._session
        if s is None:
            return []
        stalled_once = False
        while s.future or s.core.has_pending() \
                or any(i.busy for i in self.instances) \
                or self._extra_pending():
            self.step()
            if s.progressed:
                stalled_once = False
                continue
            now = time.perf_counter()
            if s.future and not s.core.has_pending():
                # open-loop idle gap: nothing in flight, next arrival in
                # the future — sleep up to it instead of busy-spinning
                time.sleep(min(max(s.future[0][0] - now, 0.0), 0.01))
            elif s.core.has_pending():
                if s.core.peek_next(s.rel(now)) is None:
                    # policy hold (carbon-aware deferral): wait for the
                    # clock/CI to move, the queue is intentionally parked
                    time.sleep(0.001)
                elif not stalled_once:
                    # a policy hold may have crossed its release boundary
                    # in the gap between step()'s select and this peek —
                    # a releasable head is only a STALL if another full
                    # step still cannot place it
                    stalled_once = True
                else:
                    raise RuntimeError(
                        "admission stalled: head request fits no instance")
        self._finalize(s)
        return s.responses

    def stats(self) -> Dict[str, float]:
        """Aggregate metrics of the last drained session."""
        return dict(self._last_stats)

    # --- internals -----------------------------------------------------------
    def _finish(self, state, inst) -> InferenceResponse:
        s = self._session
        req = s.requests[state.rid]
        t_fin = time.perf_counter()
        s.core.complete(state.rid, state.t_arrival, t_fin,
                        inst.ev.variant.accuracy)
        self.last_outputs[state.rid] = np.asarray(state.tokens, np.int64)
        t_admit = s.admit_t[state.rid]
        # TTFT runs to the first token's value on the host, in three parts:
        # queue (arrival → admission), prefill (→ last chunk dispatched)
        # and first lag (→ the token read back)
        ttft = prefill_wait = first_lag = 0.0
        if state.t_first is not None:
            ttft = state.t_first - state.t_arrival
            prefill_wait = state.t_prefilled - t_admit
            first_lag = state.t_first - state.t_prefilled
            s.ttfts.append(ttft)
            s.prefill_waits.append(prefill_wait)
            s.first_lags.append(first_lag)
        hold = self.policy.hold_info(state.rid)
        resp = InferenceResponse(
            rid=state.rid, tokens=np.asarray(state.tokens, np.int64),
            slo=req.slo, priority=req.priority, state=DONE,
            t_arrival=state.t_arrival - s.t0, t_finish=t_fin - s.t0,
            queue_delay_s=t_admit - state.t_arrival, ttft_s=ttft,
            prefill_wait_s=prefill_wait, first_lag_s=first_lag,
            latency_s=t_fin - state.t_arrival,
            energy_j=s.meters[state.rid], preemptions=state.preempts,
            accuracy=inst.ev.variant.accuracy,
            variant=inst.ev.variant.name, deadline_s=req.deadline_s,
            held_s=hold[1] - hold[0] if hold is not None else 0.0,
            release_reason=hold[2] if hold is not None else None)
        s.responses.append(resp)
        reg = s.registry
        reg.counter("requests_served").inc()
        reg.counter("tokens_generated").inc(resp.n_tokens)
        reg.histogram("latency_s").observe(resp.latency_s)
        reg.labeled("latency_s", slo_class=req.slo).observe(resp.latency_s)
        reg.histogram("queue_delay_s").observe(resp.queue_delay_s)
        if state.t_first is not None:
            reg.histogram("ttft_s").observe(ttft)
            reg.labeled("ttft_s", slo_class=req.slo).observe(ttft)
        reg.histogram("accuracy").observe(resp.accuracy)
        reg.labeled("accuracy", slo_class=req.slo).observe(resp.accuracy)
        if not resp.deadline_met:
            reg.counter("deadline_misses").inc()
        if hold is not None:
            reg.counter("holds_released").inc()
            reg.histogram("held_s").observe(resp.held_s)
        if s.tracer is not None:
            # the root lifecycle span, reconstructed now that the request's
            # bounds are known; _finalize annotates the final joules/grams
            # (the idle-floor share only exists at drain)
            sid = s.tracer.span(
                "request", state.t_arrival - s.t0, t_fin - s.t0,
                rid=state.rid, slo=req.slo, n_tokens=resp.n_tokens,
                queue_delay_s=resp.queue_delay_s,
                preemptions=state.preempts)
            s.span_ids[state.rid] = sid
            if hold is not None:
                s.tracer.span("hold", hold[0], hold[1], rid=state.rid,
                              reason=hold[2])
        return resp

    def _finalize(self, s: _Session) -> None:
        wall = time.perf_counter() - s.t0
        for inst in self.instances:       # idle floor for unaccounted wall
            idle_s = max(wall - s.accounted_s[id(inst)], 0.0)
            s.charge(inst.role, inst.chips * PM.P_IDLE_W * idle_s)
        # attribute the idle floor + carbon: per-request joules sum to the
        # engine total, gCO2 = joules × the serving window's intensity
        attributed = sum(r.energy_j for r in s.responses)
        idle_share = ((s.energy - attributed) / len(s.responses)
                      if s.responses else 0.0)
        # per-role idle remainders: whatever each role charged beyond its
        # metered per-request work (its idle floor + fp dust) spreads the
        # same way, so a response's energy_by_role sums to its energy_j and
        # role totals conserve against the session (disagg conservation)
        n_resp = max(len(s.responses), 1)
        role_rem = {
            role: (total - sum(mr.get(role, 0.0)
                               for mr in s.meters_role.values())) / n_resp
            for role, total in s.role_energy.items()}
        for r in s.responses:
            r.energy_j += idle_share
            mr = s.meters_role.get(r.rid, {})
            r.energy_by_role = {role: mr.get(role, 0.0) + rem
                                for role, rem in role_rem.items()}
            r.carbon_g = r.energy_j / 3.6e6 * self.ci_g_per_kwh
            if s.tracer is not None and r.rid in s.span_ids:
                s.tracer.annotate(s.span_ids[r.rid], energy_j=r.energy_j,
                                  carbon_g=r.carbon_g)
        self.last_latencies = s.core.latencies
        self.last_responses = s.responses
        # session deltas of the instances' lifetime counters (instances
        # survive warm reconfiguration)
        chunks = sum(getattr(i, "prefill_chunks", 0)
                     for i in self.instances) - s.chunks0
        hits = sum(getattr(i, "prefix_hit_tokens", 0)
                   for i in self.instances) - s.hits0
        copied = sum(getattr(i, "swapin_pages_copied", 0)
                     for i in self.instances) - s.swap_copied0
        saved = (sum(getattr(i, "swapin_pages_total", 0)
                     for i in self.instances) - s.swap_total0) - copied
        retraces = sum(getattr(i, "retraces", 0)
                       for i in self.instances) - s.retraces0
        syncs = sum(getattr(i, "host_syncs", 0)
                    for i in self.instances) - s.syncs0
        h2d = sum(getattr(i, "h2d_transfers", 0)
                  for i in self.instances) - s.h2d0
        dispatches = sum(getattr(i, "decode_dispatches", 0)
                         for i in self.instances) - s.dispatches0
        total_g = s.energy / 3.6e6 * self.ci_g_per_kwh
        # fold the session totals into the registry; ``_last_stats`` below
        # is a *view* over it (same samples + same nearest-rank percentile
        # as the legacy SchedulerCore path, so the numbers are identical)
        reg = s.registry
        reg.counter("energy_j").inc(s.energy)
        reg.counter("carbon_g").inc(total_g)
        reg.counter("decode_steps").inc(s.decode_steps)
        reg.counter("preemptions").inc(s.preempt_total)
        reg.counter("prefill_chunks").inc(chunks)
        reg.counter("prefix_hit_tokens").inc(hits)
        reg.counter("swapin_pages_copied").inc(copied)
        reg.counter("swapin_pages_saved").inc(saved)
        reg.counter("compile_retraces").inc(retraces)
        reg.counter("host_syncs").inc(syncs)
        reg.counter("h2d_transfers").inc(h2d)
        reg.counter("decode_dispatches").inc(dispatches)
        reg.counter("handoffs").inc(s.handoffs)
        reg.counter("handoff_pages").inc(s.handoff_pages)
        reg.gauge("wall_s").set(wall)
        served = int(reg.value("requests_served"))
        total_tokens = int(reg.value("tokens_generated"))
        lat = reg.histogram("latency_s")
        self._last_stats = {
            "served": served,
            "p50_s": lat.percentile(50.0),
            "p95_s": lat.percentile(95.0),
            "p99_s": lat.percentile(99.0),
            "mean_accuracy": reg.histogram("accuracy").mean,
            "energy_j": reg.value("energy_j"),
            "carbon_g": reg.value("carbon_g"),
            "wall_s": wall,
            "tokens": total_tokens,
            "tokens_per_s": total_tokens / max(wall, 1e-9),
            "j_per_token": s.energy / max(total_tokens, 1),
            "decode_steps": s.decode_steps,
            "mean_occupancy": (s.occ_frac_sum / s.decode_steps
                               if s.decode_steps else 0.0),
            "mean_inflight": (s.inflight_sum / s.decode_steps
                              if s.decode_steps else 0.0),
            # sequences holding cache memory per tick (decoding OR mid-
            # chunked-prefill) — the "sustained admitted concurrency" a
            # memory layout actually achieves on a given arena
            "mean_admitted": (s.admitted_sum / s.tick_samples
                              if s.tick_samples else 0.0),
            "queue_delay_p95_s":
                reg.histogram("queue_delay_s").percentile(95.0),
            "ttft_p95_s": reg.histogram("ttft_s").percentile(95.0),
            # the TTFT split past admission, over the requests that got a
            # first token (None where none did): last chunk dispatched, and
            # the first token read back after it
            "prefill_wait_p90_s": (latency_percentile(s.prefill_waits, 90.0)
                                   if s.prefill_waits else None),
            "first_lag_p90_s": (latency_percentile(s.first_lags, 90.0)
                                if s.first_lags else None),
            "blocks_peak": s.blocks_peak,
            "preemptions": s.preempt_total,
            "prefill_chunks": chunks,
            "prefix_hit_tokens": hits,
            # partial swap-in: pages a full restore would have copied vs
            # pages actually written back (the gap = tree-resident reuse)
            "swapin_pages_copied": copied,
            "partial_swapin_pages_saved": saved,
            "compile_retraces": retraces,
            # decode-hot-path traffic: blocking host round-trips, explicit
            # H2D uploads (event-driven only under pipelining), and jitted
            # decode dispatches (< decode_steps when fusion engaged)
            "host_syncs": syncs,
            "h2d_transfers": h2d,
            "decode_dispatches": dispatches,
            # disaggregation: sequences handed prefill→decode, pages moved,
            # and the per-role joules split (all zero on monolithic engines;
            # "both" carries the whole total there).  prefill + decode +
            # handoff + both == energy_j exactly — the conservation check
            # ``obs.validate.check_disagg_conservation`` enforces it.
            "handoffs": s.handoffs,
            "handoff_pages": s.handoff_pages,
            "prefill_energy_j": s.role_energy.get("prefill", 0.0),
            "decode_energy_j": s.role_energy.get("decode", 0.0),
            "handoff_energy_j": s.role_energy.get("handoff", 0.0),
            "both_energy_j": s.role_energy.get("both", 0.0),
        }
        if self.telemetry is not None and self.telemetry.feed is not None:
            # one exact segment per session: feed totals stay equal to the
            # engine's charged joules/grams with no re-derivation
            self.telemetry.feed.record_segment(self._feed_clock, wall,
                                               s.energy, total_g)
        self._feed_clock += wall
        self._session = None

    # --- bulk-prompt convenience ---------------------------------------------
    def _serve_prompts(self, prompts: Sequence[np.ndarray], n_new: int = 8,
                       arrival_s: Optional[Sequence[float]] = None
                       ) -> Dict[str, float]:
        """Method shorthand for :func:`serving.api.serve_prompts` — kept
        for ``serve_poisson`` and tests that only care about prompts; the
        public surface is the typed ``ServingBackend`` protocol (the
        ``serve(prompts=...)`` deprecation shim is gone)."""
        return serve_prompts(self, prompts, n_new, arrival_s)

    def serve_poisson(self, rate_rps: float, n_requests: int,
                      prompt_lens: Sequence[int] = (6,), n_new: int = 8,
                      seed: int = 0) -> Dict[str, float]:
        """Open-loop serving under Poisson arrivals at ``rate_rps``.

        Prompts cycle through ``prompt_lens`` (random tokens); inter-arrival
        gaps are exponential.  Returns the session stats plus the offered
        rate — at sub-saturation loads ``queue_delay_p95_s`` stays bounded,
        at saturation it grows with the run length."""
        rng = np.random.default_rng(seed)
        vocab = next(iter(self.family.values())).cfg.vocab_size
        prompts = [rng.integers(0, vocab,
                                size=(int(prompt_lens[i % len(prompt_lens)]),)
                                ).astype(np.int32)
                   for i in range(n_requests)]
        arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, n_requests))
        m = self._serve_prompts(prompts, n_new=n_new,
                                arrival_s=arrivals.tolist())
        m["offered_rps"] = rate_rps
        return m
