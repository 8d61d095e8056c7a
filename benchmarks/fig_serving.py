"""Request-level serving benchmark: trace replay, dense vs paged vs
kernel-path paged.

Replays seeded Poisson and bursty arrival traces (repro.serve.trace)
through three engines on a reduced model — the dense-slab oracle, the
paged engine on the gather paths, and the paged engine on both kernel
paths (``decode_path="kernel"``: the length-masked paged-attention
Pallas kernel run straight over the pool, no per-tick dense view;
``prefill_path="kernel"``: the tick's prompt chunks packed ragged
through the segment/causal-masked ragged-prefill kernel, token-granular
packed-KV gather instead of a dense view) — and
reports, per trace and engine: p50/p99 request latency (ticks), total
ticks, prefill/decode token counts, tokens/tick, and — for the paged
engines — pool peak/mean occupancy, preemptions, KV bytes vs the dense
engine's per-slot reservation, and the modeled per-decode-tick HBM
traffic (gather path: the full dense view it materializes; kernel
path: the pages the batch actually occupies plus the block tables).
The report is a deterministic function of (seed, sizes): engines run on
a virtual :class:`repro.obs.TickClock` and no wall-clock numbers enter
the JSON, so two runs with the same arguments emit byte-identical
reports (tests/test_serving.py gates on this, the tuner-journal
byte-identity discipline applied to serving).  Each engine block
carries a ``percentiles`` entry — queue-wait / TTFT / TPOT (ticks) and
step-time (virtual µs) p50/p95/p99 from the engine's mergeable log2
latency histograms (schema-v3 snapshot, docs/observability.md).

``--smoke`` (CI) hard-asserts the tentpole's acceptance criteria:

* three-way token identity — dense ≡ paged ≡ paged_kernel on both
  traces (and every request completes);
* the kernel arm's ``gather_bytes`` counter is exactly 0 and its
  ``kernel_decode_ticks`` counter is positive (every decode tick ran
  the kernel, none fell back);
* the kernel arm's ``kernel_prefill_ticks`` counter is positive and
  its ``prefill_gather_bytes`` (token-granular packed-KV reads) land
  below the gather arm's (full dense views per prefill tick);
* the kernel path's per-decode-tick HBM bytes are below the gather
  path's at the smoke shape;
* the poisoned-KV leakage canary: sentinel garbage written into a
  foreign sequence's packed-KV span and every padding slot leaves the
  other sequences' ragged-prefill outputs bit-identical;
* the paged pool's KV bytes are below the dense per-slot reservation,
  and peak pool utilization clears the floor;
* every engine's ``percentiles`` block is populated (queue_wait / ttft
  / tpot / step_time each carry counts) and a re-replay of the paged
  engine over the Poisson trace reproduces it exactly — the latency
  histograms are as deterministic as the token streams.

``--dispatch-table PATH`` writes a valid ``dispatch_table.json`` whose
``paged_attention`` bucket entry records, in its provenance, which
decode path won the bucket (``decode_path`` + the two modeled per-tick
byte counts).

Host-relative wall-clock throughput is printed to stdout for human
eyes only.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, "src")

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.models import build  # noqa: E402
from repro.obs import TickClock  # noqa: E402
from repro.serve import PagedServingEngine, ServingEngine  # noqa: E402
from repro.serve.metrics import ServingMetrics  # noqa: E402
from repro.serve.pool import KVPool  # noqa: E402
from repro.serve.trace import (bursty_trace, percentile,  # noqa: E402
                               poisson_trace, replay)

UTILIZATION_FLOOR = 0.4      # peak pool-page occupancy / usable pages


def _engine_report(res, *, wall_s: float) -> dict:
    lats = list(res["latency"].values())
    m = res["metrics"]
    toks = m["counters"]["prefill_tokens"] + m["counters"]["decode_tokens"]
    rep = {
        "requests": len(res["outputs"]),
        "errors": len(res["errors"]),
        "ticks": res["ticks"],
        "latency_p50": percentile(lats, 50),
        "latency_p99": percentile(lats, 99),
        "prefill_tokens": m["counters"]["prefill_tokens"],
        "decode_tokens": m["counters"]["decode_tokens"],
        "tokens_per_tick": round(toks / max(res["ticks"], 1), 6),
        "peak_queue_depth": m["peaks"]["queue_depth"],
        "peak_occupancy": m["peaks"]["occupancy"],
        "capacity": m["capacity"],
        "preemptions": m["counters"]["preempted"],
        "percentiles": ServingMetrics.from_snapshot(m)
        .latency_quantiles(),
        "metrics": m,
    }
    # stdout only — never in the report JSON (byte-identity)
    print(f"    {m['kind']}: {res['ticks']} ticks, "
          f"p50={rep['latency_p50']} p99={rep['latency_p99']} ticks, "
          f"{toks / max(wall_s, 1e-9):.0f} tok/s wall")
    return rep


def _decode_hbm_model(eng, args, model) -> dict:
    """Deterministic per-decode-tick HBM traffic model for a paged
    engine.  Gather path: every decode tick materializes the full dense
    cache view (batch × max_len, every leaf).  Kernel path: the kernel
    reads only the pages the batch occupies at peak plus the block
    tables — no dense view ever exists."""
    dense_view = KVPool.dense_reserved_bytes(model, args.slots,
                                             args.max_len)
    per_page = eng.kv.nbytes // eng.kv.n_pages
    peak_pages = eng.metrics.snapshot()["peaks"]["occupancy"]
    table_bytes = args.slots * (args.max_len // args.page_size) * 4
    kernel = peak_pages * per_page + table_bytes
    return {"gather_decode_hbm_bytes_per_tick": dense_view,
            "kernel_decode_hbm_bytes_per_tick": kernel}


def run_trace(name, trace, model, params, args) -> dict:
    print(f"  trace {name}: {len(trace)} requests")
    out = {}

    # fresh virtual clock per engine: step_time histograms become a
    # deterministic function of tick count, keeping the report
    # byte-identical across runs and hosts
    def paged(path):
        # the kernel arm exercises BOTH kernel paths: paged-attention
        # decode and ragged-prefill chunked prefill
        return lambda: PagedServingEngine(
            model, params, pool_pages=args.pool_pages,
            page_size=args.page_size, max_batch=args.slots,
            max_len=args.max_len, prefill_chunk=args.prefill_chunk,
            eos_id=-1, decode_path=path, prefill_path=path,
            clock=TickClock())

    engines = {
        "dense": lambda: ServingEngine(
            model, params, n_slots=args.slots, max_len=args.max_len,
            eos_id=-1, clock=TickClock()),
        "paged": paged("gather"),
        "paged_kernel": paged("kernel"),
    }
    results = {}
    for kind, mk in engines.items():
        eng = mk()
        t0 = time.perf_counter()
        res = replay(eng, trace)
        wall = time.perf_counter() - t0
        results[kind] = res
        out[kind] = _engine_report(res, wall_s=wall)
        if kind.startswith("paged"):
            out[kind]["pool_kv_bytes"] = eng.kv.nbytes
            out[kind]["dense_reserved_kv_bytes"] = \
                KVPool.dense_reserved_bytes(model, args.slots, args.max_len)
            out[kind]["peak_utilization"] = round(
                eng.metrics.peak_utilization(), 6)
            hbm = _decode_hbm_model(eng, args, model)
            out[kind]["decode_hbm_bytes_per_tick"] = (
                hbm["kernel_decode_hbm_bytes_per_tick"]
                if kind == "paged_kernel"
                else hbm["gather_decode_hbm_bytes_per_tick"])
            if kind == "paged_kernel":
                out[kind]["hbm_model"] = hbm
                out[kind]["kernel_cfg"] = (
                    eng._kernel_cfg.name() if eng._kernel_cfg else None)
    out["token_identical"] = (
        results["dense"]["outputs"] == results["paged"]["outputs"]
        == results["paged_kernel"]["outputs"])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pool-pages", type=int, default=25)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: assert three-way token identity, the "
                         "kernel arm's zero gather bytes + HBM win, "
                         "pool-vs-dense KV bytes, and the utilization "
                         "floor")
    ap.add_argument("--out", default=None, help="write report JSON here")
    ap.add_argument("--dispatch-table", default=None,
                    help="write a dispatch_table.json whose "
                         "paged_attention entry records the winning "
                         "decode path in its provenance")
    args = ap.parse_args(argv)

    cfg = configs.get_reduced(args.arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    traces = {
        "poisson": poisson_trace(
            seed=args.seed + 1, n_requests=args.requests, mean_gap=3.0,
            prompt_lens=(4, 28), max_new=(4, 12), vocab=cfg.vocab),
        "bursty": bursty_trace(
            seed=args.seed + 2, n_bursts=max(args.requests // 6, 1),
            burst_size=6, burst_gap=20, prompt_lens=(4, 28),
            max_new=(4, 12), vocab=cfg.vocab),
    }

    report = {
        "schema": 4,
        "arch": cfg.name,
        "config": {
            "seed": args.seed, "requests": args.requests,
            "slots": args.slots, "max_len": args.max_len,
            "page_size": args.page_size, "pool_pages": args.pool_pages,
            "prefill_chunk": args.prefill_chunk,
        },
        "traces": {},
    }
    for name, trace in traces.items():
        report["traces"][name] = run_trace(name, trace, model, params,
                                           args)

    text = json.dumps(report, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"report -> {args.out}")
    else:
        print(text)

    if args.dispatch_table:
        _write_dispatch_table(args.dispatch_table, report, cfg, args)

    if args.smoke:
        for name, tr in report["traces"].items():
            assert tr["token_identical"], \
                (f"{name}: engine outputs diverged "
                 f"(dense vs paged vs paged_kernel)")
            for kind in ("dense", "paged", "paged_kernel"):
                assert tr[kind]["errors"] == 0, f"{name}/{kind}: errors"
                assert tr[kind]["requests"] == len(traces[name]), \
                    f"{name}/{kind}: not every request completed"
            p = tr["paged"]
            assert p["pool_kv_bytes"] < p["dense_reserved_kv_bytes"], \
                (f"{name}: paged pool {p['pool_kv_bytes']}B is not below "
                 f"the dense reservation {p['dense_reserved_kv_bytes']}B")
            assert p["peak_utilization"] >= UTILIZATION_FLOOR, \
                (f"{name}: peak pool utilization "
                 f"{p['peak_utilization']:.2f} under the "
                 f"{UTILIZATION_FLOOR} floor")
            k = tr["paged_kernel"]
            kc = k["metrics"]["counters"]
            assert kc["gather_bytes"] == 0, \
                (f"{name}: kernel path gathered {kc['gather_bytes']}B "
                 f"of dense view on decode ticks")
            assert kc["kernel_decode_ticks"] > 0, \
                f"{name}: kernel path never ran the kernel"
            assert kc["kernel_prefill_ticks"] > 0, \
                f"{name}: kernel path never kernel-prefilled"
            pc = p["metrics"]["counters"]
            assert (kc["prefill_gather_bytes"]
                    < pc["prefill_gather_bytes"]), \
                (f"{name}: packed prefill gather "
                 f"{kc['prefill_gather_bytes']}B is not below the dense "
                 f"prefill views' {pc['prefill_gather_bytes']}B")
            assert (k["decode_hbm_bytes_per_tick"]
                    < p["decode_hbm_bytes_per_tick"]), \
                (f"{name}: kernel decode HBM "
                 f"{k['decode_hbm_bytes_per_tick']}B/tick is not below "
                 f"gather's {p['decode_hbm_bytes_per_tick']}B/tick")
            for kind in ("dense", "paged", "paged_kernel"):
                pct = tr[kind]["percentiles"]
                assert set(pct) == {"queue_wait", "ttft", "tpot",
                                    "step_time"}, \
                    f"{name}/{kind}: percentile kinds {sorted(pct)}"
                for lk, s in pct.items():
                    assert s["count"] > 0, \
                        f"{name}/{kind}: {lk} histogram is empty"
                    assert s["p50"] <= s["p95"] <= s["p99"], \
                        f"{name}/{kind}: {lk} quantiles not monotone"
        # latency determinism: a fresh paged engine on a fresh virtual
        # clock re-replaying the Poisson trace must reproduce the
        # percentile block exactly, not just the token streams
        eng2 = PagedServingEngine(
            model, params, pool_pages=args.pool_pages,
            page_size=args.page_size, max_batch=args.slots,
            max_len=args.max_len, prefill_chunk=args.prefill_chunk,
            eos_id=-1, decode_path="gather", clock=TickClock())
        res2 = replay(eng2, traces["poisson"])
        pct2 = ServingMetrics.from_snapshot(
            res2["metrics"]).latency_quantiles()
        assert pct2 == report["traces"]["poisson"]["paged"]["percentiles"], \
            "poisson/paged: percentile block changed on re-replay"
        _leakage_canary()
        print("SMOKE OK: dense = paged = paged_kernel tokens, kernel "
              "path gathered 0 dense-view bytes and beat the gather "
              "path's per-tick decode HBM, kernel prefill ran and "
              "packed reads beat the dense prefill views, poisoned-KV "
              "canary clean, pool below dense "
              f"reservation, utilization >= {UTILIZATION_FLOOR}, "
              "latency percentiles populated and re-replay-identical "
              "on both traces")
    return report


def _leakage_canary() -> None:
    """Poisoned-KV canary over the ragged-prefill kernel the kernel
    arm's prefill ticks run: sentinel garbage in a foreign sequence's
    packed span and in every padding slot must leave the other
    sequences' outputs bit-identical and padding rows exactly zero —
    the runtime mirror of the family's gate-conformity invariant."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.families.ragged_prefill import RaggedPrefillConfig
    from repro.kernels.ragged_prefill import (cu_seqlens, ragged_metadata,
                                              ragged_prefill_attend)

    rng = np.random.default_rng(0)
    cu = cu_seqlens([48, 64, 30])
    seg, pos = ragged_metadata(cu, 192)
    q = jnp.asarray(rng.normal(size=(4, 192, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 192, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 192, 32)), jnp.float32)
    kw = dict(cfg=RaggedPrefillConfig(block_q=32, block_kv=32),
              interpret=jax.default_backend() == "cpu")
    clean = np.asarray(ragged_prefill_attend(
        q, k, v, seg, pos, seg, pos, **kw))
    k2, v2 = np.asarray(k).copy(), np.asarray(v).copy()
    lo, hi = int(cu[1]), int(cu[2])       # sequence 1's packed span
    k2[:, lo:hi] = v2[:, lo:hi] = 1e6
    k2[:, int(cu[-1]):] = v2[:, int(cu[-1]):] = 1e6   # padding slots
    poisoned = np.asarray(ragged_prefill_attend(
        q, jnp.asarray(k2), jnp.asarray(v2), seg, pos, seg, pos, **kw))
    np.testing.assert_array_equal(clean[:, :lo], poisoned[:, :lo])
    np.testing.assert_array_equal(clean[:, hi:int(cu[-1])],
                                  poisoned[:, hi:int(cu[-1])])
    assert float(np.abs(poisoned[:, int(cu[-1]):]).max()) == 0.0, \
        "padding rows leaked poisoned KV"


def _write_dispatch_table(path, report, cfg, args) -> None:
    """Publish a valid dispatch table for the benchmarked bucket whose
    provenance records which decode path won (modeled per-tick decode
    HBM bytes, lower wins — deterministic, no wall clock)."""
    from repro.core.families.paged_attention import PagedAttentionProblem
    from repro.core.tuning import dispatch
    from repro.kernels.paged_attention.ops import default_config

    pages_per_seq = args.max_len // args.page_size
    prob = PagedAttentionProblem(
        batch=args.slots, q_heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
        seq_kv=args.max_len, page_size=args.page_size,
        pool_pages=args.pool_pages, head_dim=cfg.resolved_head_dim,
        dtype="f32")
    kcfg = default_config(pages_per_seq)
    # worst case across traces: the path must win everywhere it serves
    gather_b = max(t["paged"]["decode_hbm_bytes_per_tick"]
                   for t in report["traces"].values())
    kernel_b = max(t["paged_kernel"]["decode_hbm_bytes_per_tick"]
                   for t in report["traces"].values())
    winner = "kernel" if kernel_b < gather_b else "gather"
    hbm_per_s = 819e9                      # v5p per-chip HBM BW
    entry = {
        "config": {f: getattr(kcfg, f) for f in
                   ("block_pages",)},
        "problem": {f: getattr(prob, f) for f in
                    ("batch", "q_heads", "kv_heads", "seq_kv",
                     "page_size", "pool_pages", "head_dim", "dtype")},
        "est_ms": round(kernel_b / hbm_per_s * 1e3, 9),
        "baseline_ms": round(gather_b / hbm_per_s * 1e3, 9),
        "speedup": round(gather_b / max(kernel_b, 1), 6),
        "provenance": {
            "job": f"serving:{dispatch.shape_bucket(prob)}",
            "seed": args.seed,
            "decode_path": winner,
            "gather_decode_hbm_bytes_per_tick": gather_b,
            "kernel_decode_hbm_bytes_per_tick": kernel_b,
        },
    }
    table = dispatch.DispatchTable({
        "version": dispatch.VERSION,
        "entries": {"paged_attention":
                    {dispatch.shape_bucket(prob): entry}},
    })
    table.save(path)
    print(f"dispatch table -> {path}  "
          f"(decode_path={winner}, kernel {kernel_b}B vs "
          f"gather {gather_b}B per decode tick)")


if __name__ == "__main__":
    main()
