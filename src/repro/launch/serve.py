"""Serving launcher: continuous-batching engine over a model checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        [--engine paged] [--requests 16] [--slots 4] [--pool-pages 64]

Loads the latest checkpoint when present (otherwise fresh init), spins the
chosen engine — ``--engine paged`` (default) runs the block-table KV-pool
engine with chunked prefill and headroom admission, decode through the
paged-attention kernel and prefill through the ragged-prefill kernel;
``--engine dense`` the per-slot slab baseline — and reports completion,
throughput and the engine's metrics snapshot.  :func:`build_engine` is
the setup both :func:`main` and ``chip_smoke.py`` run.  The decode_32k /
long_500k dry-run cells exercise the same serve_step at production
shapes.

JAX's persistent compilation cache (:func:`use_compile_cache`): where
``JAX_COMPILATION_CACHE_DIR`` is set JAX keeps its cache there; otherwise
the entry points point it at ``<checkout>/.jax_cache``.

Observability (docs/observability.md): ``--metrics-port N`` serves the
live metrics snapshot in Prometheus text format at
``http://127.0.0.1:N/metrics`` from a stdlib ``http.server`` thread
(port 0 picks a free one); ``--trace-out FILE`` enables span tracing
and dumps the Perfetto-loadable Chrome trace on shutdown.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro import configs, obs
from repro.checkpoint import CheckpointManager
from repro.models import build
from repro.serve import PagedServingEngine, Request, ServingEngine

# fixed, inside the checkout and git-ignored: the cache directory is
# part of the cache key, so a path that moved between runs never hits
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call from an entry
    point before anything compiles.  ``JAX_COMPILATION_CACHE_DIR``, when
    set, is JAX's own setting and wins untouched."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--engine", choices=("paged", "dense"),
                    default="paged")
    ap.add_argument("--slots", type=int, default=4,
                    help="dense: cache slots; paged: decode batch width")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical KV pages (default: 3/4 of the dense "
                         "slot reservation)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dispatch-table", default=None,
                    help="fleet tuner dispatch_table.json with tuned "
                         "kernel configs (examples/argus_optimize.py)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text metrics on this port "
                         "(0 = pick a free one)")
    ap.add_argument("--trace-out", default=None,
                    help="enable span tracing; dump the Perfetto trace "
                         "file here on shutdown")
    return ap


def build_engine(args):
    """Model (``--seed`` init, or the latest checkpoint) and the chosen
    serving engine, from parsed :func:`parser` arguments."""
    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    ckpt_dir = Path(args.ckpt_dir or f"checkpoints/{cfg.name}")
    # look, don't create: CheckpointManager makes its directory
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir.is_dir() else None
    if mgr is not None and mgr.latest_step() is not None:
        state = mgr.restore({"params": model.abstract()})
        params = state["params"]
        print(f"restored step {state['meta']['step']} from {ckpt_dir}")

    table = None
    if args.dispatch_table:
        from repro.core.tuning import load_dispatch_table
        table = load_dispatch_table(args.dispatch_table)
        print(f"dispatch table: {table.summary()}")

    if args.engine == "dense":
        return ServingEngine(model, params, n_slots=args.slots,
                             max_len=args.max_len, eos_id=-1,
                             dispatch_table=table)
    pool_pages = args.pool_pages or max(
        2, args.slots * args.max_len * 3 // (4 * args.page_size))
    return PagedServingEngine(
        model, params, pool_pages=pool_pages,
        page_size=args.page_size, max_batch=args.slots,
        max_len=args.max_len, prefill_chunk=args.prefill_chunk,
        eos_id=-1, dispatch_table=table, decode_path="kernel",
        prefill_path="kernel")


def main(argv=None):
    args = parser().parse_args(argv)
    use_compile_cache()
    eng = build_engine(args)
    vocab = eng.model.cfg.vocab
    if args.trace_out:
        obs.enable()
    server = None
    if args.metrics_port is not None:
        from repro.obs.export import MetricsServer, prometheus_text
        server = MetricsServer(
            lambda: prometheus_text(eng.metrics.snapshot()),
            port=args.metrics_port)
        print(f"metrics: http://127.0.0.1:{server.port}/metrics")

    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        plen = int(rng.integers(4, args.max_len // 4))
        eng.submit(Request(
            rid, rng.integers(2, vocab, size=plen).tolist(),
            max_new_tokens=args.max_new_tokens))

    t0 = time.perf_counter()
    try:
        done = eng.run()
    finally:
        if server is not None:
            server.close()
        if args.trace_out:
            obs.tracer().save(args.trace_out)
            obs.disable()
            print(f"trace: {args.trace_out} "
                  f"({len(obs.tracer().events())} spans — load in "
                  f"Perfetto / chrome://tracing)")
    dt = time.perf_counter() - t0
    new_tokens = sum(len(r.output) for r in done)
    print(f"{len(done)}/{args.requests} requests complete, "
          f"{new_tokens} tokens in {dt:.2f}s "
          f"({new_tokens / dt:.1f} tok/s on this host)")
    q = eng.metrics.latency_quantiles()
    print("latency (ticks; step_time µs): " + ", ".join(
        f"{k} p50={v['p50']} p95={v['p95']} p99={v['p99']}"
        for k, v in q.items()))
    print("metrics:", json.dumps(eng.metrics.snapshot(), sort_keys=True))
    assert len(done) == args.requests
    return done


if __name__ == "__main__":
    main()
