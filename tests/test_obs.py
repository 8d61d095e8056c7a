"""Observability: the log2 histogram's merge laws (hypothesis, plus a
seeded twin that always runs), bounded-error quantiles, two-process
contention on a shared histogram file, the tracer's ring bounding /
well-nestedness / zero-allocation disabled path, and the Prometheus
exposition + HTTP scrape endpoint."""
import gc
import json
import subprocess
import sys
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.obs.export import MetricsServer, prometheus_text
from repro.obs.hist import (N_BUCKETS, LogHistogram, bucket_index,
                            bucket_upper, merge_dicts,
                            quantiles_from_values)
from repro.serve.metrics import ServingMetrics

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _hist(values):
    h = LogHistogram()
    for v in values:
        h.record(v)
    return h


# ---------------------------------------------------------------------------
# Bucket layout
# ---------------------------------------------------------------------------

class TestBuckets:
    def test_bucket_boundaries(self):
        assert bucket_index(-5) == 0
        assert bucket_index(0) == 0
        assert bucket_index(1) == 1
        assert bucket_index(2) == 2
        assert bucket_index(3) == 2
        assert bucket_index(4) == 3
        assert bucket_index(2**70) == N_BUCKETS - 1

    def test_value_lands_within_its_bucket_bounds(self):
        for v in list(range(200)) + [10**6, 2**40]:
            i = bucket_index(v)
            assert v <= bucket_upper(i)
            if i > 1:
                assert v > bucket_upper(i - 1)

    def test_upper_bound_errs_by_at_most_one_bucket_width(self):
        """The reported bound is < 2x the true value (log2 buckets)."""
        for v in range(1, 5000):
            assert v <= bucket_upper(bucket_index(v)) < 2 * v


# ---------------------------------------------------------------------------
# Merge laws: hypothesis when available, seeded twin always
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # pragma: no cover - dev-only dependency
    st = None

if st is not None:
    _VALUES = st.lists(st.integers(0, 2**40), max_size=50)

    class TestMergeLawsHypothesis:
        @settings(max_examples=200, deadline=None)
        @given(a=_VALUES, b=_VALUES)
        def test_commutative(self, a, b):
            x, y = _hist(a).merge(_hist(b)), _hist(b).merge(_hist(a))
            assert x.counts == y.counts and x.total == y.total

        @settings(max_examples=200, deadline=None)
        @given(a=_VALUES, b=_VALUES, c=_VALUES)
        def test_associative(self, a, b, c):
            ha, hb, hc = _hist(a), _hist(b), _hist(c)
            x, y = ha.merge(hb).merge(hc), ha.merge(hb.merge(hc))
            assert x.counts == y.counts and x.total == y.total

        @settings(max_examples=100, deadline=None)
        @given(a=_VALUES)
        def test_empty_is_identity(self, a):
            h = _hist(a)
            m = h.merge(LogHistogram())
            assert m.counts == h.counts and m.total == h.total

        @settings(max_examples=200, deadline=None)
        @given(a=_VALUES, b=_VALUES)
        def test_merge_then_quantile_equals_record_all(self, a, b):
            """Sharded recording then merging answers every quantile
            exactly as one histogram that saw everything — the property
            fslock.merge_save leans on."""
            merged, whole = _hist(a).merge(_hist(b)), _hist(a + b)
            assert merged.counts == whole.counts
            for q in (0.5, 0.9, 0.95, 0.99):
                assert merged.quantile(q) == whole.quantile(q)
else:
    class TestMergeLawsHypothesis:
        @pytest.mark.skip(reason="hypothesis not installed — pip "
                          "install -r requirements-dev.txt")
        def test_hypothesis_properties(self):
            pass


class TestMergeLawsSeeded:
    def test_merge_laws_and_quantiles_seeded(self):
        """Hypothesis-free twin: seeded shards must merge order-free
        and answer quantiles like the unsharded histogram."""
        rng = np.random.default_rng(0)
        for _ in range(25):
            shards = [[int(v) for v in
                       rng.integers(0, 2**20, size=rng.integers(0, 40))]
                      for _ in range(4)]
            hs = [_hist(s) for s in shards]
            fwd = hs[0].merge(hs[1]).merge(hs[2]).merge(hs[3])
            rev = hs[3].merge(hs[2]).merge(hs[1]).merge(hs[0])
            whole = _hist([v for s in shards for v in s])
            assert fwd.counts == rev.counts == whole.counts
            assert fwd.total == rev.total == whole.total
            for q in (0.5, 0.95, 0.99):
                assert fwd.quantile(q) == whole.quantile(q)


# ---------------------------------------------------------------------------
# Quantiles: exact vs the raw-value reference, error bound
# ---------------------------------------------------------------------------

class TestQuantiles:
    def test_matches_reference_nearest_rank(self):
        rng = np.random.default_rng(1)
        for _ in range(20):
            values = [int(v) for v in
                      rng.integers(0, 10**6, size=rng.integers(1, 200))]
            h = _hist(values)
            for q in (0.01, 0.5, 0.9, 0.95, 0.99, 1.0):
                assert h.quantile(q) == quantiles_from_values(values, q)

    def test_error_bounded_by_bucket_width(self):
        """The estimate is >= the true nearest-rank value and < 2x it
        (one log2 bucket of slack)."""
        rng = np.random.default_rng(2)
        for _ in range(20):
            values = sorted(int(v) for v in
                            rng.integers(1, 10**6, size=100))
            h = _hist(values)
            for q in (0.5, 0.95, 0.99):
                true = values[int(np.ceil(q * len(values))) - 1]
                est = h.quantile(q)
                assert true <= est < 2 * true, (q, true, est)

    def test_empty_histogram_reports_zero(self):
        h = LogHistogram()
        assert h.quantile(0.99) == 0
        assert h.summary() == {"count": 0, "sum": 0, "p50": 0,
                               "p95": 0, "p99": 0}

    def test_dict_round_trip_and_merge_dicts(self):
        h = _hist([0, 1, 5, 5, 300])
        d = h.to_dict()
        assert d["scheme"] == "log2"
        back = LogHistogram.from_dict(d)
        assert back.counts == h.counts and back.total == h.total
        g = _hist([7, 9000])
        assert merge_dicts(d, g.to_dict()) == h.merge(g).to_dict()
        with pytest.raises(ValueError, match="scheme"):
            LogHistogram.from_dict({"scheme": "linear", "counts": {}})


# ---------------------------------------------------------------------------
# Two processes hammering one shared histogram file
# ---------------------------------------------------------------------------

# each subprocess folds single-observation histograms into the shared
# file via merge_save_hist; any lost read-merge-write round would drop
# observations from the final counts
_HAMMER = """
import sys
sys.path.insert(0, sys.argv[4])
from repro.obs.hist import LogHistogram, merge_save_hist
wid, rounds, path = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
for i in range(rounds):
    h = LogHistogram()
    h.record(wid * 100000 + i)
    merge_save_hist(path, h)
"""


class TestSharedHistogramFile:
    @pytest.mark.multiproc
    def test_two_processes_lose_no_observations(self, tmp_path):
        path = tmp_path / "latency.json"
        rounds = 40
        procs = [subprocess.Popen(
            [sys.executable, "-c", _HAMMER, str(wid), str(rounds),
             str(path), SRC]) for wid in (1, 2)]
        for p in procs:
            assert p.wait(timeout=120) == 0
        h = LogHistogram.from_dict(json.loads(path.read_text()))
        assert h.count == 2 * rounds, \
            f"lost observations under contention: {h.count}"
        expect = _hist([w * 100000 + i for w in (1, 2)
                        for i in range(rounds)])
        assert h.counts == expect.counts and h.total == expect.total


# ---------------------------------------------------------------------------
# Tracer: ring bounding, nesting, chrome round-trip, disabled path
# ---------------------------------------------------------------------------

class TestTracer:
    def teardown_method(self):
        obs.disable()

    def test_disabled_is_the_default_and_hands_out_one_singleton(self):
        assert not obs.enabled()
        a, b = obs.span("x"), obs.span("y", {"k": 1})
        assert a is b

    def test_ring_is_bounded(self):
        obs.enable(clock=obs.TickClock(), capacity=8)
        for i in range(20):
            with obs.span("ev", {"i": i}):
                pass
        evs = obs.tracer().events()
        assert len(evs) == 8
        assert [e["args"]["i"] for e in evs] == list(range(12, 20))

    def test_nested_spans_round_trip_through_chrome_schema(self, tmp_path):
        obs.enable(clock=obs.TickClock(), pid=7)
        with obs.span("outer", {"tick": 1}):
            with obs.span("inner"):
                pass
            with obs.span("inner"):
                pass
        path = tmp_path / "t.trace.json"
        obs.tracer().save(path)
        trace = json.loads(path.read_text())
        assert trace["displayTimeUnit"] == "ms"
        evs = trace["traceEvents"]
        assert [e["name"] for e in evs] == ["inner", "inner", "outer"]
        assert all(e["ph"] == "X" and e["pid"] == 7 for e in evs)
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in evs)
        assert obs.well_nested(evs)
        outer = evs[-1]
        assert outer["args"] == {"tick": 1}
        for inner in evs[:2]:
            assert outer["ts"] <= inner["ts"]
            assert inner["ts"] + inner["dur"] \
                <= outer["ts"] + outer["dur"]

    def test_well_nested_rejects_partial_overlap_and_negatives(self):
        lane = {"ph": "X", "pid": 0, "tid": 0}
        good = [dict(lane, name="a", ts=0, dur=10),
                dict(lane, name="b", ts=2, dur=3),
                dict(lane, name="c", ts=6, dur=4)]
        assert obs.well_nested(good)
        overlap = [dict(lane, name="a", ts=0, dur=10),
                   dict(lane, name="b", ts=5, dur=10)]
        assert not obs.well_nested(overlap)
        assert not obs.well_nested([dict(lane, name="a", ts=-1, dur=2)])
        assert not obs.well_nested([dict(lane, name="a", ts=0, dur=-2)])
        # the same two intervals on different lanes are fine
        other = [dict(lane, name="a", ts=0, dur=10),
                 dict(dict(lane, tid=1), name="b", ts=5, dur=10)]
        assert obs.well_nested(other)

    def test_set_merges_late_attrs(self):
        obs.enable(clock=obs.TickClock())
        with obs.span("s", {"a": 1}) as sp:
            sp.set(b=2)
        assert obs.tracer().events()[0]["args"] == {"a": 1, "b": 2}

    def test_tick_clock_is_deterministic(self):
        a, b = obs.TickClock(), obs.TickClock()
        assert [a() for _ in range(5)] == [b() for _ in range(5)]
        assert obs.TickClock(step_us=50)() == pytest.approx(50e-6)

    @pytest.mark.skipif(not hasattr(sys, "getallocatedblocks"),
                        reason="needs sys.getallocatedblocks")
    def test_disabled_span_allocates_nothing(self):
        """The hot-path guarantee, pinned as an allocation budget over
        a tight loop — not a timing test.  The disabled path must hand
        out the shared null span without materializing anything."""
        assert not obs.enabled()
        span = obs.span
        for _ in range(1000):          # warm caches / free lists
            with span("warmup"):
                pass
        gc.collect()
        before = sys.getallocatedblocks()
        for _ in range(100_000):
            # the serving engine's call sites of one tick
            with span("serve.tick"):
                with span("serve.tables"):
                    pass
                with span("serve.launch"):
                    pass
                with span("serve.sample"):
                    pass
        delta = sys.getallocatedblocks() - before
        assert delta <= 16, \
            f"disabled span() allocated {delta} blocks over the loop"

    def test_importing_obs_loads_no_jax(self):
        code = ("import sys; sys.path.insert(0, sys.argv[1]); "
                "import repro.obs; assert 'jax' not in sys.modules")
        subprocess.run([sys.executable, "-c", code, SRC], check=True,
                       timeout=60)


# ---------------------------------------------------------------------------
# Profiler sink: spans on the JAX profiler's trace, on the device clock
# ---------------------------------------------------------------------------

def _profile_events(trace_dir):
    """Every event of the newest profile under ``trace_dir``, as
    ``(name, start_ns, dur_ns, lane, stats)``."""
    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                  key=lambda p: p.stat().st_mtime)[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, int(e.start_ns), int(e.duration_ns),
                            f"{plane.name}/{line.name}",
                            {k: v for k, v in e.stats}))
    return out


class TestProfilerSink:
    def teardown_method(self):
        obs.disable()

    def test_scalar_attrs_become_annotation_metadata(self, tmp_path):
        import jax
        tr = obs.enable(clock=obs.TickClock())
        with jax.profiler.trace(str(tmp_path)):
            with obs.span("serve.tick", {"tick": 3}) as sp:
                sp.set(decode_tokens=2, trace_ids=["req-1"])
        obs.disable()
        got = [e for e in _profile_events(tmp_path)
               if e[0] == "serve.tick"]
        assert len(got) == 1
        assert got[0][4] == {"tick": 3, "decode_tokens": 2}
        # the ring keeps every attr, as without the sink
        assert tr.events()[0]["args"] == {"tick": 3, "decode_tokens": 2,
                                          "trace_ids": ["req-1"]}

    def test_engine_phases_nest_on_the_trace(self, tmp_path):
        import jax
        from repro import configs
        from repro.models import build
        from repro.serve import PagedServingEngine, Request
        model = build(configs.get_reduced("qwen3-1.7b"))
        params = model.init(jax.random.PRNGKey(0))
        eng = PagedServingEngine(model, params, pool_pages=40, page_size=8,
                                 max_batch=3, max_len=64, prefill_chunk=8,
                                 eos_id=-1, decode_path="kernel",
                                 prefill_path="kernel")
        eng.submit(Request(0, list(range(2, 8)), max_new_tokens=6))
        eng.step()
        # two prompts packed together: a prefill geometry not met yet
        eng.submit(Request(1, list(range(3, 9)), max_new_tokens=3))
        eng.submit(Request(2, list(range(4, 9)), max_new_tokens=3))
        with jax.profiler.trace(str(tmp_path)):
            tr = obs.enable()
            for _ in range(3):
                eng.step()
            obs.disable()
        rows = [r for r in _profile_events(tmp_path)
                if r[0].startswith("serve.")]
        names = [r[0] for r in rows]
        # the trace holds the engine's spans the ring holds
        assert sorted(names) == sorted(e["name"] for e in tr.events()
                                       if e["name"].startswith("serve."))
        assert names.count("serve.tick") == 3
        for want in ("serve.tables", "serve.launch", "serve.sample",
                     "serve.pack", "serve.verify"):
            assert want in names, names
        evs = [{"name": n, "ts": s, "dur": d, "pid": 0, "tid": lane}
               for n, s, d, lane, _ in rows]
        assert obs.well_nested(evs)

        def inside(a, b):
            return b["ts"] <= a["ts"] and \
                a["ts"] + a["dur"] <= b["ts"] + b["dur"]

        by = lambda n: [e for e in evs if e["name"] == n]
        chains = [(t, d, s) for t in by("serve.tick")
                  for d in by("serve.decode_tick") if inside(d, t)
                  for s in by("serve.sample") if inside(s, d)]
        # every tick decoded: tick > decode_tick > sample, three times
        assert len({id(t) for t, _, _ in chains}) == 3


# ---------------------------------------------------------------------------
# Prometheus exposition + scrape endpoint
# ---------------------------------------------------------------------------

def _sample_metrics():
    m = ServingMetrics(24, "paged")
    for t in range(10):
        m.record_tick(queue_depth=1, active=2, occupancy=12,
                      decode_tokens=2, step_time_us=40 + t)
    m.record_latency("ttft", 3)
    m.record_latency("ttft", 9)
    m.record_latency("tpot", 1)
    m.record_latency("queue_wait", 0)
    return m


class TestPrometheus:
    def test_counters_gauges_and_labels(self):
        text = prometheus_text(_sample_metrics().snapshot())
        assert 'argus_ticks_total{engine="paged"} 10' in text
        assert 'argus_decode_tokens_total{engine="paged"} 20' in text
        assert 'argus_capacity{engine="paged"} 24' in text
        assert 'argus_occupancy_peak{engine="paged"} 12' in text
        assert "# TYPE argus_ticks_total counter" in text
        assert "# TYPE argus_ttft histogram" in text

    def test_host_loop_counters_are_exposed(self):
        m = _sample_metrics()
        m.record_tick(queue_depth=0, active=2, occupancy=12,
                      decode_tokens=2, host_syncs=2, verify_us=930)
        text = prometheus_text(m.snapshot())
        assert "# TYPE argus_host_syncs_total counter" in text
        assert 'argus_host_syncs_total{engine="paged"} 2' in text
        assert 'argus_verify_us_total{engine="paged"} 930' in text

    def test_histogram_buckets_are_cumulative_and_end_at_inf(self):
        text = prometheus_text(_sample_metrics().snapshot())
        for name, count, total in (("ttft", 2, 12), ("tpot", 1, 1),
                                   ("queue_wait", 1, 0),
                                   ("step_time", 10, sum(range(40, 50)))):
            lines = [ln for ln in text.splitlines()
                     if ln.startswith(f"argus_{name}_bucket")]
            counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
            assert counts == sorted(counts), f"{name}: not cumulative"
            assert lines[-1].startswith(
                f'argus_{name}_bucket{{engine="paged",le="+Inf"}}')
            assert counts[-1] == count
            assert f'argus_{name}_count{{engine="paged"}} {count}' in text
            assert f'argus_{name}_sum{{engine="paged"}} {total}' in text

    def test_v2_snapshot_renders_without_latency(self):
        snap = _sample_metrics().snapshot()
        del snap["latency"]
        snap["schema"] = 2
        text = prometheus_text(snap)
        assert "argus_ticks_total" in text
        assert "_bucket" not in text

    def test_metrics_server_scrape(self):
        m = _sample_metrics()
        srv = MetricsServer(lambda: prometheus_text(m.snapshot()), port=0)
        try:
            url = f"http://127.0.0.1:{srv.port}/metrics"
            with urllib.request.urlopen(url, timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
                body = resp.read().decode()
            assert 'argus_ticks_total{engine="paged"} 10' in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=10)
        finally:
            srv.close()
