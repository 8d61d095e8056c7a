"""Bring-up smoke: serve qwen3-1.7b at its published widths on one TPU.

    python chip_smoke.py [--seed 0]

Builds the engine through :func:`repro.launch.serve.build_engine` (the
launcher's own setup: paged pool, paged-attention kernel decode,
ragged-prefill kernel prefill) with random weights from ``--seed``,
serves a handful of seeded requests, one of them a prompt of 1024+
tokens that prefills in chunks over several ticks while other rows
decode, and asserts:

* every request completed, without error, with all its new tokens;
* every tick that decoded a row ran the paged-attention kernel and every
  tick that prefilled a row ran the ragged-prefill kernel (no gather or
  dense fallback), counted from the engine's per-tick metrics;
* the compiled decode and prefill steps hold the Pallas kernels as
  Mosaic custom calls (``tpu_custom_call``), not interpreted loops;
* each generated token is one the float32 reference nearly prefers: the
  same weights cast to float32 through ``model.apply`` (plain XLA, matmul
  precision "highest"), at every generated position, give the engine's
  token a logit within ``GAP_TOL`` reference standard deviations of the
  position's maximum.

It exits non-zero without a result unless JAX's first device is a TPU
and ``src/repro`` sits beside this file.  Everything it prints before
the last line is the output of one smoke run, not a benchmark.  The last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "qwen3-1.7b"
# 8 decode rows of up to 2048 positions on a 1024-page pool of 16-token
# pages (1.9 GB of bf16 KV at published widths), 256-token prefill chunks
GEOMETRY = ("--slots", "8", "--max-len", "2048", "--page-size", "16",
            "--pool-pages", "1024", "--prefill-chunk", "256")
N_REQUESTS = 10
NEW_TOKENS = (16, 32)           # inclusive range of new tokens per request
# Largest admitted gap between the reference's top logit and its logit
# for the engine's token, in units of the position's reference logit
# standard deviation.  bf16 activations move each logit by about 3-4% of
# that deviation at these widths (bf16 vs float32 XLA forward, 2-8
# layers), slowly growing with depth, and a token picked under noise e
# lies within 2e of the maximum.  A wrong mask, position or cache read
# yields an unrelated token, about 4 deviations below the maximum.
GAP_TOL = 0.15


class CompileLog:
    """Counts XLA compilations (persistent-cache hits included) through
    ``jax.monitoring``."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)

    def read(self) -> dict:
        return {"compiles": self.count,
                "compile_s": round(self.seconds, 3),
                "cache_hits": self.cache_hits}


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def make_requests(seed: int, vocab: int, max_len: int, n: int):
    """Seeded traffic: request 0 has a prompt of half to 11/16 of
    ``max_len`` (1024-1407 tokens at 2048), the others 16 to 5/16 of it;
    each asks for NEW_TOKENS new tokens."""
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(max_len // 2, max_len * 11 // 16))] + [
        int(x) for x in rng.integers(16, max_len * 5 // 16, size=n - 1)]
    return [Request(rid, rng.integers(2, vocab, size=p).tolist(),
                    max_new_tokens=int(rng.integers(NEW_TOKENS[0],
                                                    NEW_TOKENS[1] + 1)))
            for rid, p in enumerate(lens)]


def _mem(ma) -> dict:
    return {k: int(getattr(ma, f"{k}_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp")}


def serve_and_check(launch_argv, *, seed: int, n_requests: int = N_REQUESTS,
                    log=print) -> dict:
    """Serve ``make_requests`` traffic through the launcher's engine and
    check it; raises :class:`SmokeFailure` on any failed check.  Returns
    what the run measured, including, per compiled kernel step, whether
    its program text holds a ``tpu_custom_call``."""
    with CompileLog() as compiles:
        return _serve_and_check(launch_argv, seed, n_requests, log,
                                compiles)


def _serve_and_check(launch_argv, seed, n_requests, log, compiles):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve as launch
    from repro.models import build

    t0 = time.perf_counter()
    eng = launch.build_engine(launch.parser().parse_args(
        ["--arch", ARCH, "--seed", str(seed), *launch_argv]))
    jax.block_until_ready((eng.params, eng.kv.storage))
    cfg = eng.model.cfg
    out = {"setup_s": round(time.perf_counter() - t0, 3),
           "setup_compiles": compiles.read(),
           "param_bytes": sum(x.nbytes for x in jax.tree.leaves(eng.params)),
           "pool_bytes": eng.kv.nbytes}
    log(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"head_dim={cfg.resolved_head_dim} vocab={cfg.vocab}")
    log(f"bytes: params {out['param_bytes']}, KV pool {out['pool_bytes']} "
        f"({eng.alloc.n_pages} pages x {eng.page_size} tokens), "
        f"set-up {out['setup_s']} s with "
        f"{json.dumps(out['setup_compiles'])}")

    reqs = make_requests(seed, cfg.vocab, eng.max_len, n_requests)
    for r in reqs:
        eng.submit(r)
    decode_ticks = prefill_ticks = overlap_ticks = 0
    t0 = time.perf_counter()
    while eng.queue or eng.active:
        check(eng.metrics.counters["ticks"] < 10_000, "engine did not drain")
        before = dict(eng.metrics.counters)
        eng.step()
        c = eng.metrics.counters
        dec = c["decode_tokens"] > before["decode_tokens"]
        pre = c["prefill_tokens"] > before["prefill_tokens"]
        decode_ticks += dec
        prefill_ticks += pre
        overlap_ticks += dec and pre
    out["serve_s"] = round(time.perf_counter() - t0, 3)
    c = dict(eng.metrics.counters)
    out.update(ticks=c["ticks"], decode_ticks=decode_ticks,
               prefill_ticks=prefill_ticks, overlap_ticks=overlap_ticks,
               counters=c, serve_compiles=compiles.read())
    log(f"served: {len(eng.finished)}/{len(reqs)} requests, {c['ticks']} "
        f"ticks ({decode_ticks} decoded, {prefill_ticks} prefilled, "
        f"{overlap_ticks} both), {c['prefill_tokens']} prompt + "
        f"{c['decode_tokens']} decode tokens in {out['serve_s']} s wall "
        f"(compiles included)")
    log(f"counters: {json.dumps(c, sort_keys=True)}")
    log(f"compiles while serving: {json.dumps(out['serve_compiles'])}")

    done = {r.rid: r for r in eng.finished}
    check(sorted(done) == [r.rid for r in reqs], "not every request finished")
    for r in reqs:
        check(done[r.rid].error is None, f"request {r.rid}: {r.error}")
        check(len(r.output) == r.max_new_tokens,
              f"request {r.rid}: {len(r.output)}/{r.max_new_tokens} tokens")
    check(decode_ticks > 0 and c["kernel_decode_ticks"] == decode_ticks,
          f"{c['kernel_decode_ticks']} kernel decode ticks of "
          f"{decode_ticks} decoding ticks")
    check(prefill_ticks > 0 and c["kernel_prefill_ticks"] == prefill_ticks,
          f"{c['kernel_prefill_ticks']} kernel prefill ticks of "
          f"{prefill_ticks} prefilling ticks")
    check(c["gather_bytes"] == 0, f"decode gathered {c['gather_bytes']} B")
    check(overlap_ticks > 0, "no tick both prefilled and decoded")

    # the programs the device ran, compiled again at the shapes they ran
    steps = {}
    for name, lowered in eng.lower_kernel_steps().items():
        compiled = lowered.compile()
        steps[name] = {"tpu_custom_call":
                       "tpu_custom_call" in compiled.as_text(),
                       "memory": _mem(compiled.memory_analysis())}
    check("decode" in steps and len(steps) > 1,
          f"kernel steps built: {sorted(steps)}")
    out["steps"] = steps
    big = max((n for n in steps if n != "decode"),
              key=lambda n: [int(x) for x in
                             n.split("_")[1].split("x")])
    for name in ("decode", big):
        log(f"memory_analysis {name}: {json.dumps(steps[name]['memory'])}")
    log(f"kernel steps: decode + {len(steps) - 1} prefill geometries")

    # float32 reference: same weights, plain XLA, highest precision
    params, model = eng.params, eng.model
    del eng
    gc.collect()
    ref = build(dataclasses.replace(model.cfg, dtype="float32"))
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    del params
    gc.collect()

    @jax.jit
    def ref_logits(p, toks, pos, ids):
        logits, _ = ref.apply(p, toks, remat=False)
        lg = logits[0, pos, :ref.cfg.vocab]                  # (G, V)
        pick = jnp.take_along_axis(lg, ids[:, None], axis=1)[:, 0]
        return lg.max(-1) - pick, lg.std(-1), lg.argmax(-1)

    G = NEW_TOKENS[1]
    L = max(len(r.prompt) + len(r.output) - 1 for r in reqs)
    L = -(-L // 128) * 128
    worst = worst_abs = 0.0
    n_pos = n_top = 0
    t0 = time.perf_counter()
    for r in reqs:
        seq = r.prompt + r.output[:-1]
        toks = np.zeros((1, L), np.int32)
        toks[0, :len(seq)] = seq
        n = len(r.output)
        pos = np.zeros((G,), np.int32)
        ids = np.zeros((G,), np.int32)
        pos[:n] = len(r.prompt) - 1 + np.arange(n)
        ids[:n] = r.output
        with jax.default_matmul_precision("highest"):
            gap, sd, top = jax.device_get(ref_logits(
                p32, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(ids)))
        gap, sd, top = gap[:n], sd[:n], top[:n]
        rel = gap / sd
        j = int(rel.argmax())
        check(float(rel[j]) <= GAP_TOL,
              f"request {r.rid}, new token {j}: engine token {r.output[j]} "
              f"is {gap[j]:.4f} logits ({rel[j]:.4f} sd) below the "
              f"reference maximum (token {top[j]}); tolerance {GAP_TOL} sd")
        if float(rel[j]) >= worst:
            worst, worst_abs = float(rel[j]), float(gap[j])
        n_pos += n
        n_top += int((top == np.asarray(r.output)).sum())
    distinct = len({t for r in reqs for t in r.output})
    out["reference"] = {"positions": n_pos, "argmax_agree": n_top,
                        "distinct_tokens": distinct,
                        "max_gap": worst_abs, "max_gap_sd": worst,
                        "tol_sd": GAP_TOL,
                        "seconds": round(time.perf_counter() - t0, 3)}
    log(f"reference (float32, highest): {n_pos} generated positions in "
        f"{len(reqs)} requests ({distinct} distinct tokens), engine "
        f"token = reference argmax at "
        f"{n_top}; largest gap {worst_abs:.6f} logits = {worst:.6f} sd "
        f"(tolerance {GAP_TOL} sd)")
    out["compiles"] = compiles.read()
    log(f"compiles in all: {json.dumps(out['compiles'])}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is on "
              f"platform {dev.platform!r}", file=sys.stderr)
        return 1
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {src}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    from repro.launch import serve as launch

    cache = launch.use_compile_cache()
    print(f"smoke run, not a benchmark: {dev.device_kind} x{len(devices)} "
          f"({dev.platform}), compile cache {cache}")
    try:
        res = serve_and_check(GEOMETRY, seed=args.seed)
        interpreted = sorted(n for n, s in res["steps"].items()
                             if not s["tpu_custom_call"])
        check(not interpreted,
              f"no tpu_custom_call in compiled steps {interpreted}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
