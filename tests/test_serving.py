"""Paged serving: page-allocator properties (hypothesis), KV-pool
gather/scatter correctness, paged-vs-dense token identity (incl. under
pool-pressure preemption), the retirement-boundary regression, and the
fig_serving byte-identical-report determinism gate."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.serve import (PagedServingEngine, PageAllocator, PoolExhausted,
                         Request, ServingEngine)
from repro.serve.metrics import ServingMetrics
from repro.serve.pool import KVPool, NULL_PAGE, pages_needed
from repro.serve.trace import bursty_trace, percentile, poisson_trace

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Page allocator: property tests (pure bookkeeping, no jax)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # pragma: no cover - dev-only dependency
    st = None


def _drive(alloc: PageAllocator, ops, running=frozenset()):
    """Apply an op stream, swallowing expected PoolExhausted; the
    allocator's structural invariants must hold after every op."""
    for kind, seq, n in ops:
        try:
            if kind == "alloc":
                alloc.alloc(seq, n)
            elif kind == "ensure":
                alloc.ensure(seq, n * alloc.page_size)
            elif kind == "free":
                alloc.free_seq(seq)
            elif kind == "touch":
                alloc.touch(seq)
            elif kind == "evict":
                victim = alloc.lru_victim(protected=running)
                if victim is not None:
                    assert victim not in running
                    alloc.free_seq(victim)
        except PoolExhausted:
            pass
        alloc.check()


_KINDS = ("alloc", "ensure", "free", "evict", "touch")

if st is not None:
    # op stream over a small pool: (kind, seq, amount)
    _OPS = st.lists(
        st.tuples(st.sampled_from(_KINDS), st.integers(0, 5),
                  st.integers(1, 6)),
        max_size=60)

    class TestPageAllocatorProperties:
        @settings(max_examples=200, deadline=None)
        @given(ops=_OPS, n_pages=st.integers(2, 24))
        def test_no_page_mapped_twice_and_freelist_conserved(self, ops,
                                                             n_pages):
            """After any op sequence: every physical page is mapped to
            at most one sequence, the null page is never mapped, and
            free + mapped always partitions the usable pool."""
            _drive(PageAllocator(n_pages, page_size=4), ops)

        @settings(max_examples=200, deadline=None)
        @given(ops=_OPS, running=st.sets(st.integers(0, 5), max_size=4))
        def test_eviction_never_reclaims_running_sequence(self, ops,
                                                          running):
            """lru_victim(protected=running) never names a running
            sequence, no matter the interleaving of allocs, frees and
            evictions."""
            _drive(PageAllocator(9, page_size=4), ops,
                   running=frozenset(running))

        @settings(max_examples=100, deadline=None)
        @given(tok=st.integers(1, 64),
               ps=st.sampled_from([1, 2, 4, 8, 16]))
        def test_ensure_allocates_exactly_the_ceiling(self, tok, ps):
            a = PageAllocator(80, page_size=ps)
            a.ensure(0, tok)
            assert len(a.tables[0]) == pages_needed(tok, ps)
            a.ensure(0, tok)                    # idempotent
            assert len(a.tables[0]) == pages_needed(tok, ps)
else:
    class TestPageAllocatorProperties:
        @pytest.mark.skip(reason="hypothesis not installed — pip "
                          "install -r requirements-dev.txt")
        def test_hypothesis_properties(self):
            pass


class TestPageAllocator:
    def test_seeded_fuzz_conserves_pool_and_respects_protection(self):
        """Hypothesis-free twin of the property tests: a seeded random
        op stream (always runs, even without the dev deps) must keep
        every allocator invariant after each op and never evict a
        protected sequence."""
        rng = np.random.default_rng(0)
        for trial in range(40):
            n_pages = int(rng.integers(2, 25))
            running = frozenset(
                int(x) for x in rng.integers(0, 6, size=3))
            ops = [(_KINDS[int(rng.integers(len(_KINDS)))],
                    int(rng.integers(0, 6)), int(rng.integers(1, 7)))
                   for _ in range(60)]
            _drive(PageAllocator(n_pages, page_size=4), ops,
                   running=running)

    def test_ensure_allocates_exactly_the_ceiling(self):
        for ps in (1, 2, 4, 8, 16):
            for tok in (1, 3, ps, ps + 1, 4 * ps, 63):
                a = PageAllocator(80, page_size=ps)
                a.ensure(0, tok)
                assert len(a.tables[0]) == pages_needed(tok, ps)
                a.ensure(0, tok)                # idempotent
                assert len(a.tables[0]) == pages_needed(tok, ps)

    def test_alloc_is_deterministic_lowest_first(self):
        a = PageAllocator(6, page_size=4)
        assert a.alloc(0, 2) == [1, 2]
        assert a.alloc(1, 2) == [3, 4]
        a.free_seq(0)
        assert a.alloc(2, 3) == [1, 2, 5]

    def test_exhaustion_raises_and_protected_eviction_fails(self):
        a = PageAllocator(4, page_size=4)
        a.alloc(0, 3)
        with pytest.raises(PoolExhausted):
            a.alloc(1, 1)
        with pytest.raises(PoolExhausted):
            a.evict(protected=frozenset([0]))
        victim, pages = a.evict(protected=frozenset())
        assert victim == 0 and len(pages) == 3 and a.free_pages == 3


# ---------------------------------------------------------------------------
# KV pool storage: gather/scatter against a dense mirror
# ---------------------------------------------------------------------------

def _reduced_model():
    from repro import configs
    from repro.models import build
    return build(configs.get_reduced("qwen3-1.7b"))


class TestKVPool:
    def test_gather_matches_dense_mirror_and_null_page_stays_zero(self):
        model = _reduced_model()
        PS, P, B, NP = 4, 9, 2, 4
        pool = KVPool(model, P, PS)
        alloc = PageAllocator(P, PS)
        dense = model.init_cache(B, NP * PS)
        axes = model.cache_axes()

        rng = np.random.default_rng(0)
        writes = []   # (row, pos)
        for row, n_tok in ((0, 7), (1, 10)):
            alloc.ensure(row, n_tok)
            writes += [(row, p) for p in range(n_tok)]
        rows = np.array([w[0] for w in writes], np.int32)
        pos = np.array([w[1] for w in writes], np.int32)
        phys = np.array(
            [alloc.tables[r][p // PS] for r, p in writes], np.int32)
        offs = np.array([p % PS for _, p in writes], np.int32)

        # random per-(row,pos) values written into a dense view mirror
        def fill(leaf, ax):
            b, s = ax.index("batch"), ax.index("kv_seq")
            lm = np.array(jnp.moveaxis(leaf, (b, s), (0, 1)))
            for r, p in writes:
                lm[r, p] = rng.normal(size=lm.shape[2:])
            return jnp.moveaxis(jnp.asarray(lm, leaf.dtype), (0, 1),
                                (b, s))
        is_ax = lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
        dense = jax.tree.map(lambda ax, l: fill(l, ax), axes, dense,
                             is_leaf=is_ax)

        pool.scatter(dense, rows, pos, phys, offs)
        tables = np.stack([alloc.table_row(r, NP) for r in range(B)])
        view = pool.gather(jnp.asarray(tables))
        for got, want in zip(jax.tree.leaves(view),
                             jax.tree.leaves(dense)):
            np.testing.assert_array_equal(np.array(got), np.array(want))

        # the null page backs unallocated slots and must stay all-zero
        for leaf, ax in zip(jax.tree.leaves(pool.storage),
                            jax.tree.leaves(axes, is_leaf=is_ax)):
            null = jnp.take(leaf, NULL_PAGE, axis=ax.index("batch"))
            assert not np.array(null).any()

    def test_rejects_unpageable_models(self):
        class Fake:
            def cache_axes(self):
                return {"h": ("batch", "mlp")}

            def cache_shape(self, b, s):
                return {"h": jax.ShapeDtypeStruct((b, 8), jnp.float32)}
        with pytest.raises(ValueError, match="cannot be paged"):
            KVPool(Fake(), 4, 4)


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    model = _reduced_model()
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _submit_all(eng, reqs):
    for rid, (p, m) in enumerate(reqs):
        eng.submit(Request(rid, list(p), max_new_tokens=m))
    return {r.rid: r.output for r in eng.run()}


def _mixed_requests(seed=3, n=8, vocab=256):
    rng = np.random.default_rng(seed)
    return [(rng.integers(2, vocab, size=int(rng.integers(6, 28))).tolist(),
             int(rng.integers(4, 12))) for _ in range(n)]


class TestPagedEngine:
    def test_paged_matches_dense_token_for_token(self, served):
        model, params = served
        reqs = _mixed_requests()
        dense = _submit_all(ServingEngine(model, params, n_slots=4,
                                          max_len=64, eos_id=-1), reqs)
        paged = _submit_all(
            PagedServingEngine(model, params, pool_pages=40, page_size=8,
                               max_batch=4, max_len=64, prefill_chunk=8,
                               eos_id=-1), reqs)
        assert paged == dense

    def test_chunk_size_does_not_change_tokens(self, served):
        model, params = served
        reqs = _mixed_requests(seed=5, n=4)
        outs = [_submit_all(
            PagedServingEngine(model, params, pool_pages=40, page_size=8,
                               max_batch=2, max_len=64, prefill_chunk=c,
                               eos_id=-1), reqs)
            for c in (1, 4, 64)]
        assert outs[0] == outs[1] == outs[2]

    def test_preemption_resume_preserves_tokens(self, served):
        """A pool sized far below the working set forces recompute-style
        preemption; greedy decode must still produce the unpressured
        token streams, and the engine must report the evictions."""
        model, params = served
        reqs = _mixed_requests()
        roomy = _submit_all(
            PagedServingEngine(model, params, pool_pages=40, page_size=8,
                               max_batch=4, max_len=64, prefill_chunk=8,
                               eos_id=-1), reqs)
        tight = PagedServingEngine(model, params, pool_pages=9,
                                   page_size=8, max_batch=4, max_len=64,
                                   prefill_chunk=8, eos_id=-1)
        out = _submit_all(tight, reqs)
        assert out == roomy
        assert tight.metrics.counters["preempted"] > 0

    def test_admission_is_headroom_driven(self, served):
        """With a near-empty pool the queue waits even though decode
        rows are free; pages freed by retirement admit the next
        request."""
        model, params = served
        eng = PagedServingEngine(model, params, pool_pages=5, page_size=8,
                                 max_batch=4, max_len=32,
                                 prefill_chunk=8, eos_id=-1)
        eng.submit(Request(0, list(range(2, 20)), max_new_tokens=4))
        eng.submit(Request(1, list(range(2, 20)), max_new_tokens=4))
        eng.step()
        # 18-token prompt + 1 -> 3 pages of 4 usable: no room for req 1
        assert len(eng.active) == 1 and len(eng.queue) == 1
        done = eng.run()
        assert sorted(r.rid for r in done) == [0, 1]
        assert all(len(r.output) == 4 for r in done)

    def test_oversized_request_rejected_not_wedged(self, served):
        model, params = served
        eng = PagedServingEngine(model, params, pool_pages=3, page_size=8,
                                 max_batch=2, max_len=64,
                                 prefill_chunk=8, eos_id=-1)
        eng.submit(Request(0, list(range(2, 40)), max_new_tokens=4))
        eng.submit(Request(1, [2, 3, 4], max_new_tokens=3))
        done = eng.run()
        by = {r.rid: r for r in done}
        assert by[0].error and by[0].done
        assert by[1].error is None and len(by[1].output) == 3

    def test_requires_page_aligned_max_len(self, served):
        model, params = served
        with pytest.raises(ValueError, match="multiple of"):
            PagedServingEngine(model, params, pool_pages=8, page_size=8,
                               max_len=60)

    def test_block_table_oob_is_rejected(self, served):
        from repro.kernels.paged_attention.ops import (InvariantViolation,
                                                       validate_block_tables)
        model, _ = served
        bad = np.array([[0, 7]], np.int32)
        with pytest.raises(InvariantViolation, match="outside"):
            validate_block_tables(bad, model=model, page_size=8,
                                  pool_pages=4)
        cfg = validate_block_tables(np.array([[0, 1]], np.int32),
                                    model=model, page_size=8,
                                    pool_pages=4)
        assert cfg is not None

    def test_mapped_length_consistency(self, served):
        """Boundary-page regression: a row whose logical length crosses
        into page k while page k was never mapped must be rejected — as
        must over-mapping and a mapped page after a null hole; the exact
        page boundary passes."""
        from repro.kernels.paged_attention.ops import (InvariantViolation,
                                                       validate_block_tables)
        model, _ = served
        kw = dict(model=model, page_size=8, pool_pages=8)
        ok = np.array([[1, 2, 0, 0], [3, 0, 0, 0]], np.int32)
        # exact boundary: 16 tokens = exactly 2 pages; 8 = exactly 1
        assert validate_block_tables(
            ok, lengths=np.array([16, 8]), **kw) is not None
        # length 17 crosses into page 2 of row 0, which is unmapped
        with pytest.raises(InvariantViolation, match="needs 3"):
            validate_block_tables(ok, lengths=np.array([17, 8]), **kw)
        # row 1 holds a page its 0-length doesn't need
        with pytest.raises(InvariantViolation, match="row 1 maps 1"):
            validate_block_tables(ok, lengths=np.array([16, 0]), **kw)
        # a mapped page after a null hole is never a valid prefix
        holey = np.array([[1, 0, 2, 0]], np.int32)
        with pytest.raises(InvariantViolation, match="null hole"):
            validate_block_tables(holey, lengths=np.array([16]), **kw)
        # lengths shape must match the table
        with pytest.raises(InvariantViolation, match="shape"):
            validate_block_tables(ok, lengths=np.array([16]), **kw)

    def test_inactive_rows_validate_with_zero_length(self, served):
        from repro.kernels.paged_attention.ops import validate_block_tables
        model, _ = served
        t = np.array([[1, 2], [0, 0]], np.int32)
        assert validate_block_tables(
            t, model=model, page_size=8, pool_pages=8,
            lengths=np.array([9, 0])) is not None


class TestKernelDecodePath:
    """decode_path="kernel": the length-masked paged-attention kernel
    replaces the per-tick decode gather — token-identical to the gather
    path (itself the dense engine's twin), zero dense-view bytes."""

    def test_rejects_unknown_decode_path(self, served):
        model, params = served
        with pytest.raises(ValueError, match="decode_path"):
            PagedServingEngine(model, params, pool_pages=8, page_size=8,
                               max_len=32, decode_path="oracle")

    def test_kernel_matches_gather_token_for_token(self, served):
        model, params = served
        reqs = _mixed_requests(seed=11, n=4)
        outs, engs = {}, {}
        for path in ("gather", "kernel"):
            eng = PagedServingEngine(model, params, pool_pages=40,
                                     page_size=8, max_batch=4, max_len=64,
                                     prefill_chunk=8, eos_id=-1,
                                     decode_path=path)
            outs[path] = _submit_all(eng, reqs)
            engs[path] = eng
        assert outs["kernel"] == outs["gather"]
        kc = engs["kernel"].metrics.counters
        gc = engs["gather"].metrics.counters
        # kernel path: every decode tick ran the kernel, none gathered
        assert kc["gather_bytes"] == 0
        assert kc["kernel_decode_ticks"] > 0
        # gather path: the inverse
        assert gc["kernel_decode_ticks"] == 0
        assert gc["gather_bytes"] > 0

    def test_kernel_path_survives_preemption(self, served):
        model, params = served
        reqs = _mixed_requests(seed=11, n=4)
        roomy = _submit_all(
            PagedServingEngine(model, params, pool_pages=40, page_size=8,
                               max_batch=4, max_len=64, prefill_chunk=8,
                               eos_id=-1, decode_path="kernel"), reqs)
        tight = PagedServingEngine(model, params, pool_pages=9,
                                   page_size=8, max_batch=4, max_len=64,
                                   prefill_chunk=8, eos_id=-1,
                                   decode_path="kernel")
        assert _submit_all(tight, reqs) == roomy
        assert tight.metrics.counters["preempted"] > 0
        assert tight.metrics.counters["gather_bytes"] == 0


@pytest.fixture
def device_reads(monkeypatch):
    """Counts the reads of a device array's value back to the host that
    go through Python (``int``, ``float``, ``item``, ``device_get``, ...;
    on the CPU ``np.asarray`` reads through the buffer protocol, which
    this does not see): the blocking reads ``host_syncs`` must count."""
    from jax._src.array import ArrayImpl
    n = [0]
    value, item = ArrayImpl._value, ArrayImpl.item

    def counted_value(self):
        n[0] += 1
        return value.fget(self)

    def counted_item(self, *args):
        n[0] += 1
        return item(self, *args)

    monkeypatch.setattr(ArrayImpl, "_value", property(counted_value))
    monkeypatch.setattr(ArrayImpl, "item", counted_item)
    return n


class TestHostLoopCounters:
    """Schema v5's host-loop counters: ``host_syncs`` counts the device
    reads the engine blocks on, at the read; ``verify_us`` the first
    tick's verification of its geometry."""

    def test_device_reads_are_counted_once_each(self, device_reads):
        x = jnp.argmax(jnp.arange(5))
        int(x), float(x), x.item(), jax.device_get(x)
        assert device_reads[0] == 4

    @pytest.mark.parametrize("path,pool_pages", [
        ("gather", 40), ("kernel", 40), ("kernel", 9)])
    def test_host_syncs_are_decode_tokens_plus_prompt_completions(
            self, served, device_reads, path, pool_pages):
        """The next tokens are picked on the device and read back once
        per sampling phase: one read for the decode batch, one for the
        prompts a tick completes, whatever the number of rows."""
        model, params = served
        reqs = _mixed_requests(seed=11, n=4)
        dense = _submit_all(ServingEngine(model, params, n_slots=4,
                                          max_len=64, eos_id=-1), reqs)
        reads0 = device_reads[0]
        eng = PagedServingEngine(model, params, pool_pages=pool_pages,
                                 page_size=8, max_batch=4, max_len=64,
                                 prefill_chunk=8, eos_id=-1,
                                 decode_path=path, prefill_path=path)
        for rid, (p, m) in enumerate(reqs):
            eng.submit(Request(rid, list(p), max_new_tokens=m))
        reqs_by_rid = {r.rid: r for r in eng.queue}
        emitted = lambda: sum(len(r.output) for r in reqs_by_rid.values())
        per_tick = []
        while eng.queue or eng.active:
            c0, e0 = dict(eng.metrics.counters), emitted()
            eng.step()
            c = eng.metrics.counters
            decoded = c["decode_tokens"] - c0["decode_tokens"]
            # every token a tick emits is a decode row's or a prompt's
            completed = emitted() - e0 - decoded
            per_tick.append((c["host_syncs"] - c0["host_syncs"],
                             decoded, completed))
        c = eng.metrics.counters
        assert c["host_syncs"] == device_reads[0] - reads0 > 0
        for syncs, decoded, completed in per_tick:
            assert syncs == (decoded > 0) + (completed > 0)
            assert syncs <= 2 and (syncs <= 1 or completed)
        # several rows decode on one tick, yet each tick reads once:
        # fewer reads than tokens emitted (one each before)
        assert max(d for _, d, _ in per_tick) > 1
        assert c["host_syncs"] < sum(d + k for _, d, k in per_tick)
        # the tight pool preempts: resumed prompts complete once more
        assert (c["preempted"] > 0) == (pool_pages == 9)
        assert {r.rid: r.output for r in eng.finished} == dense

    def test_dense_engine_counts_its_readbacks(self, served, device_reads):
        model, params = served
        reqs = _mixed_requests(seed=13, n=4)
        eng = ServingEngine(model, params, n_slots=2, max_len=64, eos_id=-1)
        outs = _submit_all(eng, reqs)
        c = eng.metrics.counters
        assert c["host_syncs"] == device_reads[0] \
            == sum(len(o) for o in outs.values()) \
            == c["decode_tokens"] + len(reqs)
        assert c["verify_us"] == 0

    @pytest.mark.parametrize("path", ["gather", "kernel"])
    def test_first_tick_verifies_its_geometry(self, served, path):
        from repro.obs import TickClock
        model, params = served
        clock = TickClock(step_us=50)
        eng = PagedServingEngine(model, params, pool_pages=40, page_size=8,
                                 max_batch=2, max_len=64, prefill_chunk=8,
                                 eos_id=-1, decode_path=path,
                                 prefill_path=path, clock=clock)
        eng.submit(Request(0, list(range(2, 8)), max_new_tokens=4))
        eng.step()                 # the whole prompt, then one decode
        first = eng.metrics.counters["verify_us"]
        # on a virtual clock each gate reads it twice: one step apart
        assert first > 0 and first % 50 == 0
        eng.step()
        eng.step()
        # the same geometries again: nothing new to verify
        assert eng.metrics.counters["verify_us"] == first


def _compile_counter():
    """Counts XLA compiles and persistent-cache loads while registered,
    as the benchmark's ``window_compiles`` does (``jax.monitoring``)."""
    n = [0]

    def on_duration(event, secs, **_):
        n[0] += event == "/jax/core/compile/backend_compile_duration"

    def on_event(event, **_):
        n[0] += event == "/jax/compilation_cache/cache_hits"

    return n, on_duration, on_event


class TestDeviceGreedy:
    """The paged engine picks each tick's next tokens inside its jitted
    steps and reads them back in one transfer per sampling phase."""

    @pytest.mark.parametrize("case", ["decode", "packed_prefill"])
    def test_batched_greedy_matches_per_row_argmax(self, case):
        from repro.serve.engine import greedy_tokens
        rng = np.random.default_rng(7)
        V = 37
        if case == "decode":
            # (B, 1, V): row i's token at [i, 0]
            logits = rng.integers(0, 3, size=(6, 1, V)).astype(np.float32)
            picks = [(i, 0) for i in range(6)]
        else:
            # (1, TQ, V): chunks of 3, 1, 5 and 2 tokens packed and
            # padded to 16; row i's token at its last query, q_last
            logits = rng.integers(0, 3, size=(1, 16, V)).astype(np.float32)
            picks = [(0, int(t)) for t in np.cumsum([3, 1, 5, 2]) - 1]
        # ties everywhere (integer logits in [0, 3)), plus a flat row and
        # a row whose maximum its last two entries share
        flat, pair = picks[0], picks[-1]
        logits[flat] = 1.0
        logits[pair] = 0.0
        logits[pair][-2:] = 2.0
        got = np.asarray(jax.jit(greedy_tokens)(jnp.asarray(logits)))
        assert got.dtype == np.int32 and got.shape == logits.shape[:-1]
        for at in picks:
            assert got[at] == int(jnp.argmax(jnp.asarray(logits[at])))
        assert got[flat] == 0 and got[pair] == V - 2

    def test_full_batch_and_two_completions_read_back_twice(
            self, served, device_reads):
        """A kernel-path tick on which every row decodes and two prompts
        complete reads the device twice: once for the completed prompts,
        once for the batch; the tokens are the dense engine's."""
        model, params = served
        reqs = [(list(range(2 + k, 9 + k)), 10) for k in range(4)]
        dense = _submit_all(ServingEngine(model, params, n_slots=4,
                                          max_len=64, eos_id=-1), reqs)
        eng = PagedServingEngine(model, params, pool_pages=40, page_size=8,
                                 max_batch=4, max_len=64, prefill_chunk=8,
                                 eos_id=-1, decode_path="kernel",
                                 prefill_path="kernel")
        for rid, (p, m) in enumerate(reqs[:2]):
            eng.submit(Request(rid, list(p), max_new_tokens=m))
        eng.step()
        for rid, (p, m) in enumerate(reqs[2:], start=2):
            eng.submit(Request(rid, list(p), max_new_tokens=m))
        c0, reads0 = dict(eng.metrics.counters), device_reads[0]
        eng.step()
        c = eng.metrics.counters
        assert c["decode_tokens"] - c0["decode_tokens"] == eng.max_batch
        assert c["prefill_tokens"] - c0["prefill_tokens"] == 14
        assert c["kernel_decode_ticks"] - c0["kernel_decode_ticks"] == 1
        assert c["kernel_prefill_ticks"] - c0["kernel_prefill_ticks"] == 1
        assert c["host_syncs"] - c0["host_syncs"] == 2
        assert device_reads[0] - reads0 == 2
        assert {r.rid: r.output for r in eng.run()} == dense

    def test_built_steps_compile_nothing_more(self, served):
        """Greedy selection lives inside the jitted steps: once a
        geometry's steps are built, ticks at it compile nothing."""
        model, params = served
        reqs = _mixed_requests(seed=11, n=4)
        eng = PagedServingEngine(model, params, pool_pages=40, page_size=8,
                                 max_batch=4, max_len=64, prefill_chunk=8,
                                 eos_id=-1, decode_path="kernel",
                                 prefill_path="kernel")
        first = _submit_all(eng, reqs)
        n, on_duration, on_event = _compile_counter()
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        try:
            again = _submit_all(eng, [(p, m) for p, m in reqs])
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
            jax.monitoring.unregister_event_listener(on_event)
        assert eng.metrics.counters["kernel_prefill_ticks"] > 0
        assert n[0] == 0
        assert again == first

    def test_kernel_steps_return_tokens_not_logits(self, served):
        model, params = served
        eng = PagedServingEngine(model, params, pool_pages=40, page_size=8,
                                 max_batch=2, max_len=64, prefill_chunk=8,
                                 eos_id=-1, decode_path="kernel",
                                 prefill_path="kernel")
        eng.submit(Request(0, list(range(2, 8)), max_new_tokens=2))
        eng.step()
        steps = eng.lower_kernel_steps()
        assert sorted(steps) == ["decode", "prefill_64x64_1"]
        want = {"decode": (2, 1), "prefill_64x64_1": (1, 64)}
        for name, lowered in steps.items():
            tokens, _ = lowered.out_info
            assert (tokens.shape, tokens.dtype) == (want[name], jnp.int32)


class TestRetirementBoundary:
    """Regression for the `pos >= max_len - 1` off-by-one: a sequence
    admitted at pos == max_len - 2 still owns the final writable cache
    position, so it decodes twice (3 tokens incl. the prefill token),
    not once."""

    def test_dense_uses_final_writable_position(self, served):
        model, params = served
        ml = 32
        eng = ServingEngine(model, params, n_slots=1, max_len=ml,
                            eos_id=-1)
        eng.submit(Request(0, list(range(2, 2 + ml - 2)),
                           max_new_tokens=10))
        (done,) = eng.run()
        assert len(done.output) == 3, \
            f"expected 3 tokens (prefill + 2 decode ticks), got " \
            f"{len(done.output)} — retirement boundary regressed"

    def test_paged_matches_dense_at_the_boundary(self, served):
        model, params = served
        ml = 32
        prompt = list(range(2, 2 + ml - 2))
        dense = ServingEngine(model, params, n_slots=1, max_len=ml,
                              eos_id=-1)
        dense.submit(Request(0, prompt, max_new_tokens=10))
        paged = PagedServingEngine(model, params, pool_pages=10,
                                   page_size=8, max_batch=1, max_len=ml,
                                   prefill_chunk=8, eos_id=-1)
        paged.submit(Request(0, prompt, max_new_tokens=10))
        assert dense.run()[0].output == paged.run()[0].output


# ---------------------------------------------------------------------------
# Metrics snapshot versioning (schema v3 with v2 back-compat)
# ---------------------------------------------------------------------------

class TestMetricsSchema:
    def _v3_snapshot(self):
        m = ServingMetrics(16, "paged")
        m.record_tick(queue_depth=1, active=2, occupancy=9,
                      decode_tokens=2, step_time_us=55)
        m.record_latency("ttft", 4)
        m.record_latency("tpot", 1)
        m.record_latency("queue_wait", 0)
        return m.snapshot()

    def test_v2_snapshot_loads_with_empty_latency(self):
        """A pre-latency (schema 2) snapshot still loads — latency
        defaults to empty histograms, the v4 prefill counters and the
        v5 host-loop counters to 0 — and re-snapshots at the current
        version."""
        from repro.serve.metrics import SCHEMA_VERSION
        snap = self._v3_snapshot()
        v2 = {k: v for k, v in snap.items() if k != "latency"}
        v2["schema"] = 2
        v2["counters"] = {k: v for k, v in snap["counters"].items()
                          if k not in ("kernel_prefill_ticks",
                                       "prefill_gather_bytes",
                                       "host_syncs", "verify_us")}
        m = ServingMetrics.from_snapshot(v2)
        assert m.counters == snap["counters"]
        assert all(h.count == 0 for h in m.latency.values())
        rt = m.snapshot()
        assert rt["schema"] == SCHEMA_VERSION
        assert all(d == {"scheme": "log2", "counts": {}, "sum": 0}
                   for d in rt["latency"].values())

    def test_v3_snapshot_loads_without_prefill_counters(self):
        """A schema-3 snapshot predates the prefill-path counters: they
        are optional on load (default 0) but a v3 snapshot carrying a
        key outside its schema is still rejected."""
        snap = self._v3_snapshot()
        v3 = dict(snap, schema=3)
        v3["counters"] = {k: v for k, v in snap["counters"].items()
                          if k not in ("kernel_prefill_ticks",
                                       "prefill_gather_bytes",
                                       "host_syncs", "verify_us")}
        m = ServingMetrics.from_snapshot(v3)
        assert m.counters["kernel_prefill_ticks"] == 0
        assert m.counters["prefill_gather_bytes"] == 0
        bad = dict(v3)
        bad["counters"] = dict(v3["counters"], bogus=1)
        with pytest.raises(ValueError, match="counters keys"):
            ServingMetrics.from_snapshot(bad)

    def test_unknown_versions_rejected_naming_the_version(self):
        snap = self._v3_snapshot()
        for bad in (1, 6, 99, None):
            with pytest.raises(ValueError, match=f"schema {bad!r}"):
                ServingMetrics.from_snapshot({**snap, "schema": bad})

    def test_v4_snapshot_loads_without_host_loop_counters(self):
        """A schema-4 snapshot predates ``host_syncs`` and
        ``verify_us``: they load as 0, its prefill counters are kept,
        and a v4 snapshot missing a v4 counter is still rejected."""
        m = ServingMetrics(16, "paged")
        m.record_tick(queue_depth=0, active=1, occupancy=3,
                      prefill_tokens=8, kernel_prefill_ticks=1,
                      prefill_gather_bytes=512, host_syncs=1,
                      verify_us=70)
        snap = m.snapshot()
        v4 = dict(snap, schema=4)
        v4["counters"] = {k: v for k, v in snap["counters"].items()
                          if k not in ("host_syncs", "verify_us")}
        got = ServingMetrics.from_snapshot(v4)
        assert got.counters["host_syncs"] == 0
        assert got.counters["verify_us"] == 0
        assert got.counters["prefill_gather_bytes"] == 512
        assert got.snapshot()["schema"] == 5
        bad = dict(v4)
        bad["counters"] = {k: v for k, v in v4["counters"].items()
                           if k != "kernel_prefill_ticks"}
        with pytest.raises(ValueError, match="counters keys"):
            ServingMetrics.from_snapshot(bad)

    def test_v5_round_trips_host_loop_counters(self):
        m = ServingMetrics(16, "paged")
        m.record_tick(queue_depth=0, active=2, occupancy=4,
                      decode_tokens=2, host_syncs=2, verify_us=1234)
        snap = m.snapshot()
        assert snap["schema"] == 5
        assert snap["counters"]["host_syncs"] == 2
        assert snap["counters"]["verify_us"] == 1234
        assert ServingMetrics.from_snapshot(snap).snapshot() == snap

    def test_v3_round_trips_latency_exactly(self):
        snap = self._v3_snapshot()
        assert ServingMetrics.from_snapshot(snap).snapshot() == snap

    def test_v3_with_wrong_latency_keys_rejected(self):
        snap = self._v3_snapshot()
        snap["latency"] = {"ttft": snap["latency"]["ttft"]}
        with pytest.raises(ValueError, match="latency keys"):
            ServingMetrics.from_snapshot(snap)


# ---------------------------------------------------------------------------
# Trace replay determinism (fig_serving byte-identity gate)
# ---------------------------------------------------------------------------

class TestTraces:
    def test_traces_are_seed_deterministic(self):
        a = poisson_trace(seed=7, n_requests=10, mean_gap=2.0)
        b = poisson_trace(seed=7, n_requests=10, mean_gap=2.0)
        assert a == b
        c = bursty_trace(seed=7, n_bursts=3, burst_size=4, burst_gap=10)
        d = bursty_trace(seed=7, n_bursts=3, burst_size=4, burst_gap=10)
        assert c == d
        assert [e.tick for e in c] == sorted(e.tick for e in c)

    def test_percentile_is_nearest_rank(self):
        v = list(range(1, 101))
        assert percentile(v, 50) == 50
        assert percentile(v, 99) == 99
        assert percentile([], 50) == 0
        assert percentile([5], 99) == 5

    @pytest.mark.slow
    def test_fig_serving_report_is_byte_identical(self, served, tmp_path):
        """Replaying the same seeded arrival trace twice yields
        byte-identical report JSON — the tuner-journal byte-identity
        discipline applied to the serving benchmark."""
        sys.path.insert(0, str(REPO / "benchmarks"))
        try:
            import fig_serving
        finally:
            sys.path.pop(0)
        argv = ["--requests", "8", "--max-len", "32", "--page-size", "8",
                "--pool-pages", "13", "--prefill-chunk", "8", "--smoke"]
        f1, f2 = tmp_path / "a.json", tmp_path / "b.json"
        fig_serving.main(argv + ["--out", str(f1)])
        fig_serving.main(argv + ["--out", str(f2)])
        assert f1.read_bytes() == f2.read_bytes()
        rep = json.loads(f1.read_text())
        assert rep["schema"] == 4
        assert rep["traces"]["poisson"]["token_identical"]
        assert rep["traces"]["bursty"]["token_identical"]
        pct = rep["traces"]["poisson"]["paged"]["percentiles"]
        assert set(pct) == {"queue_wait", "ttft", "tpot", "step_time"}
        assert all(s["count"] > 0 for s in pct.values())
