"""Continuous-batching serving engines: dense slots and paged pool.

Two engines share one request model and one metrics contract:

:class:`ServingEngine` — the dense baseline.  A fixed decode batch of
``n_slots`` sequences, each reserving a dense ``max_len`` cache slab;
free slots refill from the queue via single-sequence one-shot prefill.
Kept as the oracle the paged engine must match token-for-token, and as
the fallback for models whose cache carries positionless state leaves
(recurrent/hybrid) that cannot be paged.

:class:`PagedServingEngine` — the production layout.  KV lives in a
shared block-table page pool (:mod:`repro.serve.pool`): admission is
driven by pool headroom rather than slot reservation, prompts prefill
in fixed-size chunks interleaved with decode ticks (a long prompt never
stalls the running batch), and pool pressure preempts the
least-recently-admitted sequence back to the queue (recompute-style
resume: deterministic greedy decode makes the continuation identical).
Every tick's gather is gated by the ``paged_attention`` family's ARGUS
invariants via :func:`repro.kernels.paged_attention.ops
.validate_block_tables`, with the kernel config resolved from the
installed fleet ``dispatch_table.json`` — the engine stays the flagship
consumer of the tuner's output.

``decode_path="kernel"`` replaces the per-tick decode gather with the
length-masked paged-attention Pallas kernel run straight over the pool:
each decode tick scatters the fresh K/V inside
:meth:`~repro.models.transformer.TransformerLM.decode_step_paged` and
attends through ``(pool, block_tables, lengths)`` exactly as the engine
holds them — zero dense-view bytes materialized (the ``gather_bytes``
counter stays at 0 on decode ticks).  The kernel config is resolved per
shape bucket from the installed dispatch table and statically verified
once per batch geometry; when no verified config exists for the bucket
(or the model's cache cannot be paged-attended, e.g. MLA) the tick
falls back to the gather path, and the metrics show it: the tick adds
a dense view to ``gather_bytes`` and nothing to ``kernel_decode_ticks``
(prefill alike, with ``prefill_gather_bytes`` and
``kernel_prefill_ticks``).  Per-sequence ``lengths`` (the token
being written included) are re-validated against each row's mapped page
count every kernel tick — the boundary-page consistency check on the
hot path.

``prefill_path="kernel"`` does the same for chunked prefill: the tick's
prompt chunks are packed ragged (cu_seqlens-derived segment ids and
positions, :mod:`repro.kernels.ragged_prefill.packing`) and attended
through the segment/causal-masked ragged-prefill kernel straight off
the pool via :meth:`~repro.models.transformer.TransformerLM
.prefill_chunk_packed` — the KV read is a token-granular packed gather
(``prefill_gather_bytes`` counts it), never a dense view.  The kernel
config is resolved per packed geometry and statically verified against
the ``ragged_prefill`` family's leakage invariants
(:func:`repro.kernels.ragged_prefill.ops.verified_config`); when the
geometry is unverifiable or the model cannot packed-prefill (MLA), the
tick falls back to the dense ``decode_chunk`` path.

Kernel configs come from the fleet tuner's ``dispatch_table.json``
(:mod:`repro.core.tuning.dispatch`): pass ``dispatch_table=`` (a path or
a loaded table) and the engine installs it process-wide, so every
validated kernel entry point reached under decode (paged/flash decode,
quantized GEMMs, ...) resolves its config from the tuned table's shape
buckets instead of the shape-adaptive defaults.  The install is
deliberately process-global (the kernel entry points have no engine
handle): one table per process, last install wins.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tuning import dispatch as _dispatch
from repro import obs as _obs

from .metrics import ServingMetrics
from .pool import KVPool, PageAllocator, PoolExhausted, pages_needed


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 32
    output: List[int] = field(default_factory=list)
    done: bool = False
    error: Optional[str] = None
    trace_id: Optional[str] = None   # span correlation id (defaults rid)

    @property
    def trace_name(self) -> str:
        return self.trace_id or f"req-{self.rid}"


@dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0          # next write offset in the cache


class _HostReads:
    """The engines' one way to read a device value back to the host:
    each read blocks until the device has computed it, and counts one
    toward the tick's ``host_syncs``.  The dense engine reads each row's
    next token on its own (``_to_host``); the paged engine reads a
    sampling phase's next tokens, picked on the device, in one transfer
    (``_to_host_array``): one read for the decode batch, one for the
    prompts a prefill tick completes."""

    _host_syncs = 0

    def _to_host(self, x) -> int:
        self._host_syncs += 1
        return int(x)

    def _to_host_array(self, x) -> np.ndarray:
        self._host_syncs += 1
        return jax.device_get(x)

    def _take_host_syncs(self) -> int:
        n, self._host_syncs = self._host_syncs, 0
        return n


def greedy_tokens(logits: jnp.ndarray) -> jnp.ndarray:
    """The greedy next token at every position of ``logits`` (..., V):
    the first index of the largest logit, ``jnp.argmax``'s rule and the
    dense engine's.  Traced inside the paged engine's jitted steps, so a
    tick's tokens leave the device as one int32 array, not as logits."""
    return jnp.argmax(logits, axis=-1)


def _greedy_step(step):
    """``step`` (params, cache, tokens (B, S), pos) -> (logits (B, S, V),
    cache) returning the greedy tokens (B, S) in place of the logits."""

    def greedy_step(params, cache, tokens, pos):
        logits, cache = step(params, cache, tokens, pos)
        return greedy_tokens(logits), cache

    return greedy_step


class ServingEngine(_HostReads):
    """Dense-slab slot engine (the paged engine's token oracle)."""

    def __init__(self, model, params, *, n_slots: int = 4,
                 max_len: int = 512, eos_id: int = 1,
                 greedy: bool = True, dispatch_table=None, clock=None):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.greedy = greedy
        # step-time clock (seconds): injectable so benchmarks can pass a
        # virtual TickClock and keep reports byte-identical
        self._clock = clock or time.perf_counter
        self._lat: Dict[int, Dict[str, int]] = {}   # rid -> tick stamps
        # tuned kernel configs: install the fleet dispatch table so the
        # validated kernel entry points under decode consult it
        self.dispatch = (_dispatch.install(dispatch_table)
                         if dispatch_table is not None
                         else _dispatch.active())
        self.cache = model.init_cache(n_slots, max_len)
        self.slots = [_Slot() for _ in range(n_slots)]
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.metrics = ServingMetrics(capacity=n_slots, kind="dense")
        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(
            lambda p, t: model.prefill(p, t, max_len))

    # -- API ---------------------------------------------------------------
    def submit(self, req: Request) -> None:
        tick = self.metrics.counters["ticks"]
        self._lat[req.rid] = {"submit": tick, "queued": tick}
        self.queue.append(req)

    def _insert_cache(self, slot: int, src_cache: Dict) -> None:
        """Copy a batch-1 prefill cache into slot ``slot``.  The batch axis
        position varies per leaf (layer-stacked leaves carry a leading
        "layers" axis) — the model's declared cache_axes() names it."""
        axes = self.model.cache_axes()

        def ins(ax, dst, src):
            b = ax.index("batch")
            idx = [0] * dst.ndim
            idx[b] = slot
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), tuple(idx))

        is_axes_leaf = lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
        self.cache = jax.tree.map(ins, axes, self.cache, src_cache,
                                  is_leaf=is_axes_leaf)

    def _admit(self) -> Dict[str, int]:
        admitted = prefill_tokens = 0
        tick = self.metrics.counters["ticks"]
        for i, s in enumerate(self.slots):
            if s.req is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            toks = jnp.asarray([req.prompt], jnp.int32)
            logits, cache1 = self._prefill(self.params, toks)
            self._insert_cache(i, cache1)
            nxt = self._to_host(jnp.argmax(logits[0, -1]))
            req.output.append(nxt)
            s.req, s.pos = req, len(req.prompt)
            admitted += 1
            prefill_tokens += len(req.prompt)
            # one-shot prefill emits the first token at admission: both
            # queue-wait and TTFT resolve on this tick
            lat = self._lat.setdefault(req.rid, {"submit": tick,
                                                 "queued": tick})
            self.metrics.record_latency("queue_wait", tick - lat["queued"])
            self.metrics.record_latency("ttft", tick - lat["submit"])
            lat["last"] = tick
        return {"admitted": admitted, "prefill_tokens": prefill_tokens}

    def step(self) -> int:
        """One engine tick: admit, decode, retire.  Returns #active."""
        t0 = self._clock()
        adm = self._admit()
        tick = self.metrics.counters["ticks"]
        active = [i for i, s in enumerate(self.slots) if s.req is not None]
        finished = 0
        if active:
            tokens = np.zeros((self.n_slots, 1), np.int32)
            pos_vec = np.zeros((self.n_slots,), np.int32)
            for i in active:
                tokens[i, 0] = self.slots[i].req.output[-1]
                pos_vec[i] = self.slots[i].pos
            # per-slot write offsets: slots with heterogeneous prompt
            # lengths each write/attend at their own position (decode_step
            # vmaps the cache update over the batch dim)
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(pos_vec))
            for i in active:
                s = self.slots[i]
                nxt = self._to_host(jnp.argmax(logits[i, -1]))
                s.req.output.append(nxt)
                s.pos += 1
                lat = self._lat.get(s.req.rid)
                if lat is not None:
                    self.metrics.record_latency(
                        "tpot", tick - lat.get("last", tick))
                    lat["last"] = tick
                # retire only once the final writable position (max_len-1)
                # has been used: s.pos is the *next* write offset, so the
                # boundary is pos == max_len, not max_len - 1 (a sequence
                # admitted at pos == max_len - 2 still owns one tick)
                exhausted = (len(s.req.output) >= s.req.max_new_tokens
                             or nxt == self.eos_id
                             or s.pos >= self.max_len)
                if exhausted:
                    s.req.done = True
                    self.finished.append(s.req)
                    self._lat.pop(s.req.rid, None)
                    s.req = None
                    finished += 1
        occ = sum(1 for s in self.slots if s.req is not None)
        self.metrics.record_tick(
            queue_depth=len(self.queue), active=occ, occupancy=occ,
            decode_tokens=len(active), finished=finished,
            host_syncs=self._take_host_syncs(),
            step_time_us=int((self._clock() - t0) * 1e6), **adm)
        return len(active)

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        for _ in range(max_ticks):
            if not self.queue and all(s.req is None for s in self.slots):
                break
            self.step()
        return self.finished


# ---------------------------------------------------------------------------
# Paged engine
# ---------------------------------------------------------------------------

@dataclass
class _Seq:
    req: Request
    ctx: List[int]            # prompt (+ regenerated output on resume)
    pos: int = 0              # tokens whose KV is in the pool
    prefilled: bool = False
    admitted_at: int = 0      # admission stamp (preemption order)
    resumed: bool = False     # re-admitted after a preemption


class PagedServingEngine(_HostReads):
    """Paged continuous batching over a shared block-table KV pool.

    ``max_batch`` bounds the decode call's width (a jit shape, not a
    reservation); admission is governed by pool headroom: a request is
    admitted the moment the free list can hold its prompt plus one
    decode page.  ``max_len`` (logical positions per sequence) must be
    a multiple of ``page_size`` so the gathered view's kv length equals
    the dense engine's — that is what makes the two engines
    token-identical on the same trace.
    """

    def __init__(self, model, params, *, pool_pages: int,
                 page_size: int = 16, max_batch: int = 8,
                 max_len: int = 512, prefill_chunk: int = 32,
                 eos_id: int = 1, greedy: bool = True,
                 dispatch_table=None, decode_path: str = "gather",
                 prefill_path: str = "gather", clock=None):
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} must be a multiple of "
                             f"page_size {page_size}")
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if decode_path not in ("gather", "kernel"):
            raise ValueError(f"decode_path must be 'gather' or 'kernel', "
                             f"got {decode_path!r}")
        if prefill_path not in ("gather", "kernel"):
            raise ValueError(f"prefill_path must be 'gather' or 'kernel', "
                             f"got {prefill_path!r}")
        self.model = model
        self.params = params
        self.page_size = page_size
        self.max_batch = max_batch
        self.max_len = max_len
        self.pages_per_seq = max_len // page_size
        self.prefill_chunk = min(prefill_chunk, max_len)
        self.eos_id = eos_id
        self.greedy = greedy
        self.dispatch = (_dispatch.install(dispatch_table)
                         if dispatch_table is not None
                         else _dispatch.active())
        self.alloc = PageAllocator(pool_pages, page_size)
        self.kv = KVPool(model, pool_pages, page_size)
        self.rows: List[Optional[_Seq]] = [None] * max_batch
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.metrics = ServingMetrics(capacity=self.alloc.usable_pages,
                                      kind="paged")
        # the gather paths' steps: greedy tokens (B, S), not logits
        self._decode = jax.jit(_greedy_step(model.decode_step))
        self._chunk = (jax.jit(_greedy_step(model.decode_chunk))
                       if hasattr(model, "decode_chunk") else None)
        self._clock = clock or time.perf_counter
        self._lat: Dict[int, Dict[str, int]] = {}   # rid -> tick stamps
        self._admission_stamp = 0
        self._next_seq_id = 0
        self._table_sig = None
        self._verify_us = 0      # this tick's verification time (metrics)
        # kernel decode path: config verified per batch geometry, dense-
        # view bytes for the gather-path HBM accounting.  The Pallas
        # kernels lower for the TPU and run interpreted on the CPU (tests,
        # reduced smokes); no other backend may silently interpret them
        backend = jax.default_backend()
        if "kernel" in (decode_path, prefill_path) \
                and backend not in ("cpu", "tpu"):
            raise ValueError(f"kernel paths run on the TPU (interpreted "
                             f"on the CPU), not on backend {backend!r}")
        self._interpret = backend == "cpu"
        self.decode_path = decode_path
        self._kernel_sig = None
        self._kernel_cfg = None
        self._kernel_fn = None
        self._view_bytes = KVPool.dense_reserved_bytes(
            model, max_batch, max_len)
        # kernel prefill path: verified config + jit closure memoized per
        # packed geometry; per-token pool bytes for the packed-KV gather
        # accounting
        self.prefill_path = prefill_path
        self._prefill_cfgs: Dict = {}
        self._prefill_fns: Dict = {}
        self._token_bytes = KVPool.dense_reserved_bytes(
            model, 1, page_size) // page_size

    # -- API ---------------------------------------------------------------
    def submit(self, req: Request) -> None:
        tick = self.metrics.counters["ticks"]
        self._lat[req.rid] = {"submit": tick, "queued": tick}
        self.queue.append(req)

    @property
    def active(self) -> List[_Seq]:
        return [s for s in self.rows if s is not None]

    # -- admission ----------------------------------------------------------
    def _seq_id(self, s: _Seq) -> int:
        return s.admitted_at

    def _admit(self) -> Dict[str, int]:
        admitted = 0
        tick = self.metrics.counters["ticks"]
        while self.queue:
            req = self.queue[0]
            row = next((i for i, r in enumerate(self.rows) if r is None),
                       None)
            if row is None:
                break
            ctx = list(req.prompt) + list(req.output)
            need = pages_needed(len(ctx) + 1, self.page_size)
            if need > self.alloc.usable_pages or len(ctx) >= self.max_len:
                # can never fit: reject rather than wedge the queue
                self.queue.pop(0)
                req.done, req.error = True, "request exceeds pool capacity"
                self.finished.append(req)
                self._lat.pop(req.rid, None)
                continue
            if need > self.alloc.free_pages:
                break                      # headroom gate: wait for pages
            self._admission_stamp += 1
            seq = _Seq(req=req, ctx=ctx,
                       admitted_at=self._admission_stamp,
                       resumed=bool(req.output))
            self.queue.pop(0)
            self.alloc.ensure(self._seq_id(seq), len(ctx) + 1)
            self.rows[row] = seq
            admitted += 1
            lat = self._lat.setdefault(req.rid, {"submit": tick,
                                                 "queued": tick})
            wait = tick - lat.get("queued", tick)
            self.metrics.record_latency("queue_wait", wait)
            if _obs.enabled():
                with _obs.span("serve.admit_request") as sp:
                    sp.set(trace_id=req.trace_name, wait_ticks=wait,
                           resumed=seq.resumed)
        return {"admitted": admitted}

    # -- pool pressure -------------------------------------------------------
    def _preempt_for(self, seq: _Seq, n_tokens: int) -> int:
        """Grow seq's table to hold ``n_tokens``, evicting the least-
        recently-admitted *other* sequence back to the queue when the
        free list runs dry.  Returns the number of preemptions."""
        preempted = 0
        while True:
            try:
                self.alloc.ensure(self._seq_id(seq), n_tokens)
                return preempted
            except PoolExhausted:
                protected = frozenset([self._seq_id(seq)])
                victims = [s for s in self.active
                           if s is not seq and not s.req.done]
                if not victims:
                    raise PoolExhausted(
                        f"rid {seq.req.rid} needs "
                        f"{pages_needed(n_tokens, self.page_size)} pages; "
                        "pool exhausted with nothing evictable")
                victim = max(victims, key=lambda s: s.admitted_at)
                assert self._seq_id(victim) not in protected
                self._evict(victim)
                preempted += 1

    def _evict(self, victim: _Seq) -> None:
        """Recompute-style preemption: drop the victim's pages and requeue
        it at the front; on re-admission its context is re-prefilled as
        prompt + generated-so-far, and greedy decode continues
        identically."""
        sp = _obs.span("serve.preempt")
        with sp:
            if _obs.enabled():
                sp.set(trace_id=victim.req.trace_name, pos=victim.pos)
            self.alloc.free_seq(self._seq_id(victim))
            self.rows[self.rows.index(victim)] = None
            self.queue.insert(0, victim.req)
        # queue-wait restarts at the eviction tick (TTFT keeps running)
        lat = self._lat.get(victim.req.rid)
        if lat is not None:
            lat["queued"] = self.metrics.counters["ticks"]

    # -- gather through the validated block tables ---------------------------
    def _tables(self) -> np.ndarray:
        t = np.zeros((self.max_batch, self.pages_per_seq), np.int32)
        for i, s in enumerate(self.rows):
            if s is not None:
                t[i] = self.alloc.table_row(self._seq_id(s),
                                            self.pages_per_seq)
        return t

    def _verify(self, gate, *args, **kwargs):
        """Run an ARGUS gate on a geometry not met before, inside a
        ``serve.verify`` span; its time on the engine's clock goes to
        the tick's ``verify_us``."""
        t0 = self._clock()
        try:
            with _obs.span("serve.verify"):
                return gate(*args, **kwargs)
        finally:
            self._verify_us += round((self._clock() - t0) * 1e6)

    def _gather(self) -> Dict:
        with _obs.span("serve.tables"):
            tables = self._tables()
            sig = (tables.shape, self.alloc.n_pages)
            if sig != self._table_sig:
                # ARGUS gate: verify the paged_attention family's
                # indirection invariants for this batch geometry (config
                # resolved from the installed dispatch table) before the
                # gather consumes it
                from repro.kernels.paged_attention.ops import \
                    validate_block_tables
                self._verify(
                    validate_block_tables, tables, model=self.model,
                    page_size=self.page_size, pool_pages=self.alloc.n_pages)
                self._table_sig = sig
            else:
                # geometry already verified: still range-check the
                # concrete mapping (the runtime mirror of assert_in_range)
                if tables.min() < 0 or tables.max() >= self.alloc.n_pages:
                    raise ValueError("block table maps outside the pool")
        with _obs.span("serve.launch"):
            return self.kv.gather(jnp.asarray(tables))

    # -- prefill -------------------------------------------------------------
    def _prefill_tick(self) -> Dict[str, int]:
        """Advance every un-prefilled sequence by one prompt chunk, all
        rows batched through a single decode_chunk call."""
        pend = [(i, s) for i, s in enumerate(self.rows)
                if s is not None and not s.prefilled]
        empty = {"prefill_tokens": 0, "preempted": 0, "finished": 0}
        if not pend:
            return empty
        C = self.prefill_chunk if self._chunk is not None else 1
        preempted = 0
        for i, s in pend:
            if self.rows[i] is not s:      # evicted by an earlier ensure
                continue
            n = min(C, len(s.ctx) - s.pos)
            preempted += self._preempt_for(s, s.pos + n)
        # a preemption may have evicted a sequence in `pend` — rebuild
        pend = [(i, s) for i, s in pend if self.rows[i] is s]
        if not pend:
            return dict(empty, preempted=preempted)
        lens = {i: min(C, len(s.ctx) - s.pos) for i, s in pend}
        gather_bytes = kernel_ticks = 0
        packed = (self._prefill_kernel(pend, lens)
                  if self.prefill_path == "kernel" else None)
        if packed is not None:
            next_tokens, at, packed_kv_tokens = packed
            gather_bytes = packed_kv_tokens * self._token_bytes
            kernel_ticks = 1
        else:
            # dense decode_chunk path (default, and the fallback when
            # the packed geometry is unverifiable)
            tokens = np.zeros((self.max_batch, C), np.int32)
            pos_vec = np.zeros((self.max_batch,), np.int32)
            for i, s in pend:
                tokens[i, :lens[i]] = s.ctx[s.pos:s.pos + lens[i]]
                pos_vec[i] = s.pos
            view = self._gather()
            fn = self._chunk if self._chunk is not None else self._decode
            with _obs.span("serve.launch"):
                next_tokens, view = fn(self.params, view,
                                       jnp.asarray(tokens),
                                       jnp.asarray(pos_vec))
                self._scatter(view, {i: (s.pos, lens[i]) for i, s in pend})
            at = {i: (i, lens[i] - 1) for i, s in pend}
            gather_bytes = self._view_bytes
        total = 0
        finished = 0
        tick = self.metrics.counters["ticks"]
        with _obs.span("serve.sample"):
            # one read for every prompt this tick completes, none when
            # no prompt completes
            host_tokens = (self._to_host_array(next_tokens)
                           if any(s.pos + lens[i] == len(s.ctx)
                                  for i, s in pend) else None)
            for i, s in pend:
                s.pos += lens[i]
                total += lens[i]
                if s.pos == len(s.ctx):
                    # prompt complete: first generated token is the
                    # greedy pick at the chunk's last real position (the
                    # dense engine's argmax(prefill_logits[-1]) twin)
                    nxt = int(host_tokens[at[i]])
                    s.req.output.append(nxt)
                    s.prefilled = True
                    lat = self._lat.get(s.req.rid)
                    if lat is not None:
                        if "last" not in lat:
                            # first token ever for this request: TTFT
                            self.metrics.record_latency(
                                "ttft", tick - lat.get("submit", tick))
                        else:
                            # resumed prefill replays a decode tick: TPOT
                            self.metrics.record_latency(
                                "tpot", tick - lat["last"])
                        lat["last"] = tick
                    # a *resumed* prefill replays a decode tick, so its
                    # token gets the decode-tick exhaustion check (fresh
                    # admissions mirror the dense engine, which checks
                    # only on decode)
                    if s.resumed and (
                            len(s.req.output) >= s.req.max_new_tokens
                            or nxt == self.eos_id
                            or s.pos >= self.max_len):
                        s.req.done = True
                        self.finished.append(s.req)
                        self._lat.pop(s.req.rid, None)
                        self.alloc.free_seq(self._seq_id(s))
                        self.rows[i] = None
                        finished += 1
        return {"prefill_tokens": total, "preempted": preempted,
                "finished": finished,
                "prefill_gather_bytes": gather_bytes,
                "kernel_prefill_ticks": kernel_ticks}

    def _prefill_kernel(self, pend, lens):
        """Kernel-path chunked prefill: pack the tick's prompt chunks
        ragged and attend them through the ragged-prefill kernel
        straight off the pool (token-granular packed-KV gather, no
        dense view).  Returns ``(greedy tokens (1, TQp) on the device,
        {row: index of its last real token in them}, packed kv
        tokens)``, or None when the model cannot packed-prefill (MLA / no
        hook) or the packed geometry has no verified config — the tick
        then falls back to the dense ``decode_chunk`` path."""
        model = self.model
        if self._chunk is None \
                or not hasattr(model, "prefill_chunk_packed") \
                or getattr(model.cfg, "attn_type", None) == "mla":
            return None
        from repro.kernels.ragged_prefill.ops import verified_config
        spans = [(i, s, s.pos, lens[i]) for i, s in pend]
        # pad both packed extents to 64-token granularity: bounds the
        # jit-recompile variety while keeping pow2 blocks available
        # (64 is itself a valid block size, so every padded extent
        # tiles, with blocks the TPU compiler accepts — the gate checks
        # them) and the packed read below the dense batch view at small
        # shapes
        pad = lambda t: -(-max(t, 1) // 64) * 64
        TQp = pad(sum(n for *_, n in spans))
        TKp = pad(sum(p + n for _, _, p, n in spans))
        mcfg = model.cfg
        key = (TQp, TKp, len(spans))
        if key not in self._prefill_cfgs:
            # ARGUS gate: verify the leakage invariants once per packed
            # geometry, config resolved from the dispatch table
            self._prefill_cfgs[key] = self._verify(
                verified_config, TQp, TKp, len(spans),
                q_heads=mcfg.n_heads, kv_heads=mcfg.n_kv_heads,
                head_dim=mcfg.resolved_head_dim,
                dtype="bf16" if "bf" in str(mcfg.dtype) else "f32")
        kcfg = self._prefill_cfgs[key]
        if kcfg is None:
            return None
        with _obs.span("serve.pack"):
            operands, q_last = self._pack(spans, TQp, TKp)
        fn = self._prefill_fns.get(key)
        with _obs.span("serve.launch"):
            if fn is None:
                interp = self._interpret

                def serve_prefill_packed(prm, pool, tok, sq, pq, sk, pk,
                                         wp, wo, gp, go):
                    logits, pool = model.prefill_chunk_packed(
                        prm, pool, tok, sq, pq, sk, pk, wp, wo, gp, go,
                        kernel_cfg=kcfg, interpret=interp)
                    return greedy_tokens(logits), pool

                fn = jax.jit(serve_prefill_packed)
                self._prefill_fns[key] = fn
            next_tokens, self.kv.storage = fn(
                self.params, self.kv.storage,
                *(jnp.asarray(a) for a in operands))
        return next_tokens, {i: (0, t) for i, t in q_last.items()}, TKp

    def _pack(self, spans, TQp: int, TKp: int):
        """The packed-prefill step's host operands for ``spans``, in its
        argument order, and ``{row: packed index of its last query
        token}``."""
        PS = self.page_size
        tokens = np.zeros((1, TQp), np.int32)
        seg_q = np.full((TQp,), -1, np.int32)
        pos_q = np.zeros((TQp,), np.int32)
        seg_k = np.full((TKp,), -1, np.int32)
        pos_k = np.zeros((TKp,), np.int32)
        # padding queries write past the pool (dropped); padding KV
        # reads the reserved null page (zeros, fully masked)
        wphys = np.full((TQp,), self.alloc.n_pages, np.int32)
        woffs = np.zeros((TQp,), np.int32)
        gphys = np.zeros((TKp,), np.int32)
        goffs = np.zeros((TKp,), np.int32)
        qt = kt = 0
        q_last = {}
        for j, (i, s, p, n) in enumerate(spans):
            table = self.alloc.table_row(self._seq_id(s),
                                         self.pages_per_seq)
            tokens[0, qt:qt + n] = s.ctx[p:p + n]
            seg_q[qt:qt + n] = j
            qpos = np.arange(p, p + n)
            pos_q[qt:qt + n] = qpos
            wphys[qt:qt + n] = table[qpos // PS]
            woffs[qt:qt + n] = qpos % PS
            seg_k[kt:kt + p + n] = j
            kpos = np.arange(p + n)
            pos_k[kt:kt + p + n] = kpos
            gphys[kt:kt + p + n] = table[kpos // PS]
            goffs[kt:kt + p + n] = kpos % PS
            q_last[i] = qt + n - 1
            qt += n
            kt += p + n
        return ((tokens, seg_q, pos_q, seg_k, pos_k, wphys, woffs, gphys,
                 goffs), q_last)

    # -- decode --------------------------------------------------------------
    def _kernel_config(self, tables: np.ndarray):
        """Resolve + statically verify the kernel config for this batch
        geometry (memoized on it, like ``_gather``'s gate).  None when
        the bucket has no verified config or the cache cannot be
        paged-attended (MLA) — the tick then falls back to the gather
        path."""
        sig = (tables.shape, self.alloc.n_pages)
        if sig != self._kernel_sig:
            from repro.kernels.paged_attention.ops import (
                InvariantViolation, validate_block_tables)
            self._kernel_sig = sig
            self._kernel_fn = None
            if not hasattr(self.model, "decode_step_paged"):
                self._kernel_cfg = None
                return None
            try:
                self._kernel_cfg = self._verify(
                    validate_block_tables, tables, model=self.model,
                    page_size=self.page_size,
                    pool_pages=self.alloc.n_pages)
            except InvariantViolation:
                self._kernel_cfg = None
        return self._kernel_cfg

    def _decode_kernel(self, rows, tokens, pos_vec):
        """Kernel-path decode tick: no gather, no dense view.  The fresh
        K/V scatter happens inside ``decode_step_paged``; inactive rows
        carry null tables and length 0.  Returns the greedy tokens (B, 1)
        on the device, or None when no verified config exists for this
        geometry (gather fallback)."""
        with _obs.span("serve.tables"):
            tables = self._tables()
            cfg = self._kernel_config(tables)
            if cfg is None:
                return None
            # kernel tables: only decoding rows expose their pages — a
            # row mid-prefill holds pages for tokens not yet written,
            # which the mapped-length consistency check (rightly) rejects
            kt = np.zeros_like(tables)
            lengths = np.zeros((self.max_batch,), np.int32)
            for i, s in rows:
                kt[i] = tables[i]
                lengths[i] = s.pos + 1   # the token being written included
            # hot-path concrete gate: range + mapped-length consistency
            # (each row maps exactly ceil(length/page_size) pages, no
            # null holes)
            from repro.kernels.paged_attention.ops import \
                validate_block_tables
            validate_block_tables(kt, page_size=self.page_size,
                                  pool_pages=self.alloc.n_pages,
                                  lengths=lengths)
        with _obs.span("serve.launch"):
            if self._kernel_fn is None:
                kc, interp, model = cfg, self._interpret, self.model

                def serve_decode_step(p, pool, t, tok, pos, lens):
                    logits, pool = model.decode_step_paged(
                        p, pool, t, tok, pos, lens, kernel_cfg=kc,
                        interpret=interp)
                    return greedy_tokens(logits), pool

                self._kernel_fn = jax.jit(serve_decode_step)
            next_tokens, self.kv.storage = self._kernel_fn(
                self.params, self.kv.storage, jnp.asarray(kt),
                jnp.asarray(tokens), jnp.asarray(pos_vec),
                jnp.asarray(lengths))
        return next_tokens

    def lower_kernel_steps(self) -> Dict[str, "jax.stages.Lowered"]:
        """Lower every kernel-path step this engine has built, at the
        shapes it ran: ``decode`` and one ``prefill_<TQ>x<TK>_<n>`` per
        packed geometry.  ``.compile()`` on each gives the program the
        device runs: its ``as_text()`` (on the TPU the Pallas kernels
        show as ``tpu_custom_call``) and its ``memory_analysis()``."""
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        B, NP = self.max_batch, self.pages_per_seq
        out = {}
        if self._kernel_fn is not None:
            out["decode"] = self._kernel_fn.lower(
                self.params, self.kv.storage, i32(B, NP), i32(B, 1),
                i32(B), i32(B))
        for (tq, tk, n), fn in sorted(self._prefill_fns.items()):
            out[f"prefill_{tq}x{tk}_{n}"] = fn.lower(
                self.params, self.kv.storage, i32(1, tq), i32(tq),
                i32(tq), i32(tk), i32(tk), i32(tq), i32(tq), i32(tk),
                i32(tk))
        return out

    def _decode_tick(self) -> Dict[str, int]:
        rows = [(i, s) for i, s in enumerate(self.rows)
                if s is not None and s.prefilled and not s.req.done]
        if not rows:
            return {"decode_tokens": 0, "finished": 0, "preempted": 0}
        preempted = 0
        for i, s in rows:
            if self.rows[i] is not s:      # evicted by an earlier ensure
                continue
            preempted += self._preempt_for(s, s.pos + 1)
        rows = [(i, s) for i, s in rows if self.rows[i] is s]
        if not rows:
            return {"decode_tokens": 0, "finished": 0,
                    "preempted": preempted}
        tokens = np.zeros((self.max_batch, 1), np.int32)
        pos_vec = np.zeros((self.max_batch,), np.int32)
        for i, s in rows:
            tokens[i, 0] = s.req.output[-1]
            pos_vec[i] = s.pos
        gather_bytes = kernel_ticks = 0
        next_tokens = (self._decode_kernel(rows, tokens, pos_vec)
                       if self.decode_path == "kernel" else None)
        if next_tokens is None:
            view = self._gather()
            with _obs.span("serve.launch"):
                next_tokens, view = self._decode(self.params, view,
                                                 jnp.asarray(tokens),
                                                 jnp.asarray(pos_vec))
                self._scatter(view, {i: (s.pos, 1) for i, s in rows})
            gather_bytes = self._view_bytes
        else:
            kernel_ticks = 1
        finished = 0
        tick = self.metrics.counters["ticks"]
        with _obs.span("serve.sample"):
            # the whole batch's next tokens in one read
            host_tokens = self._to_host_array(next_tokens)
            for i, s in rows:
                nxt = int(host_tokens[i, 0])
                s.req.output.append(nxt)
                s.pos += 1
                s.ctx.append(int(tokens[i, 0]))
                lat = self._lat.get(s.req.rid)
                if lat is not None:
                    self.metrics.record_latency(
                        "tpot", tick - lat.get("last", tick))
                    lat["last"] = tick
                exhausted = (len(s.req.output) >= s.req.max_new_tokens
                             or nxt == self.eos_id
                             or s.pos >= self.max_len)
                if exhausted:
                    s.req.done = True
                    self.finished.append(s.req)
                    self._lat.pop(s.req.rid, None)
                    self.alloc.free_seq(self._seq_id(s))
                    self.rows[i] = None
                    finished += 1
        return {"decode_tokens": len(rows), "finished": finished,
                "preempted": preempted, "gather_bytes": gather_bytes,
                "kernel_decode_ticks": kernel_ticks}

    def _scatter(self, view: Dict, slabs: Dict[int, tuple]) -> None:
        """slabs: row -> (start position, n tokens written)."""
        rows, pos, phys, offs = [], [], [], []
        for i, (p0, n) in slabs.items():
            s = self.rows[i]
            table = self.alloc.tables[self._seq_id(s)]
            for p in range(p0, p0 + n):
                rows.append(i)
                pos.append(p)
                phys.append(table[p // self.page_size])
                offs.append(p % self.page_size)
        self.kv.scatter(view, np.asarray(rows, np.int32),
                        np.asarray(pos, np.int32),
                        np.asarray(phys, np.int32),
                        np.asarray(offs, np.int32))

    # -- tick ----------------------------------------------------------------
    def step(self) -> int:
        """One engine tick: admit by headroom, one prefill chunk per
        pending prompt, one decode step for the running batch, retire.
        Returns #active sequences."""
        t0 = self._clock()
        tick_sp = _obs.span("serve.tick")
        with tick_sp:
            with _obs.span("serve.admit"):
                adm = self._admit()
            with _obs.span("serve.prefill_chunk"):
                pre = self._prefill_tick()
            dec_sp = _obs.span("serve.decode_tick")
            with dec_sp:
                dec = self._decode_tick()
                if _obs.enabled():
                    dec_sp.set(
                        decode_tokens=dec["decode_tokens"],
                        trace_ids=[s.req.trace_name for s in self.active
                                   if s.prefilled])
            for s in self.active:
                self.alloc.touch(self._seq_id(s))
            n_active = len(self.active)
            if _obs.enabled():
                tick_sp.set(tick=self.metrics.counters["ticks"],
                            active=n_active,
                            decode_tokens=dec["decode_tokens"],
                            prefill_tokens=pre["prefill_tokens"])
            verify_us, self._verify_us = self._verify_us, 0
            self.metrics.record_tick(
                queue_depth=len(self.queue), active=n_active,
                occupancy=self.alloc.used_pages,
                prefill_tokens=pre["prefill_tokens"],
                decode_tokens=dec["decode_tokens"],
                admitted=adm["admitted"],
                finished=pre["finished"] + dec["finished"],
                preempted=pre["preempted"] + dec["preempted"],
                gather_bytes=dec.get("gather_bytes", 0),
                kernel_decode_ticks=dec.get("kernel_decode_ticks", 0),
                kernel_prefill_ticks=pre.get("kernel_prefill_ticks", 0),
                prefill_gather_bytes=pre.get("prefill_gather_bytes", 0),
                host_syncs=self._take_host_syncs(),
                verify_us=verify_us,
                step_time_us=int((self._clock() - t0) * 1e6))
        return n_active

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        for _ in range(max_ticks):
            if not self.queue and not self.active:
                break
            self.step()
        return self.finished
