"""The served entry points off the chip: the launcher's shared engine
setup, its compile-cache rule, the engine's backend guard, fleet
workers kept off the chip, and ``chip_smoke.py`` — refusing every
platform but the TPU, and running its whole check end to end on the CPU
(kernels interpreted) at the reduced qwen3-1.7b preset."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import serve as launch

ROOT = Path(__file__).resolve().parents[1]
# a small serving geometry for the reduced preset: 4 rows, 128
# positions, 16-token prefill chunks (the long prompt takes 4-6 ticks)
SMALL = ("--reduced", "--slots", "4", "--max-len", "128", "--page-size",
         "16", "--pool-pages", "48", "--prefill-chunk", "16")


def _smoke_module():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_smoke(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


class TestLauncher:
    def test_build_engine_serves_through_both_kernel_paths(self, tmp_path):
        args = launch.parser().parse_args(
            ["--arch", "qwen3-1.7b", "--ckpt-dir", str(tmp_path / "none"),
             *SMALL])
        eng = launch.build_engine(args)
        assert (eng.decode_path, eng.prefill_path) == ("kernel", "kernel")
        assert not (tmp_path / "none").exists(), \
            "looking for a checkpoint created its directory"

    def test_main_completes_every_request_on_the_kernel_paths(
            self, tmp_path, monkeypatch, capsys):
        # the variable is set: the launcher leaves the cache to JAX
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        done = launch.main(["--arch", "qwen3-1.7b", "--requests", "3",
                            "--max-new-tokens", "4", "--ckpt-dir",
                            str(tmp_path / "none"), *SMALL])
        assert len(done) == 3
        assert all(r.error is None and len(r.output) == 4 for r in done)
        assert '"kernel_prefill_ticks": 0' not in capsys.readouterr().out

    def test_compile_cache_env_wins_untouched(self, monkeypatch, tmp_path):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert launch.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_compile_cache_default_is_fixed_in_the_checkout(
            self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = launch.use_compile_cache()
            assert path == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        ignored = (ROOT / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored


class TestBackendGuard:
    def test_kernel_paths_refuse_a_backend_without_lowering(
            self, monkeypatch):
        from repro.serve import PagedServingEngine
        args = launch.parser().parse_args(
            ["--arch", "qwen3-1.7b", "--reduced", "--engine", "dense"])
        eng = launch.build_engine(args)
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(ValueError, match="backend 'gpu'"):
            PagedServingEngine(eng.model, eng.params, pool_pages=8,
                               max_len=64, decode_path="kernel")
        # the gather paths need no kernel lowering
        PagedServingEngine(eng.model, eng.params, pool_pages=8, max_len=64)


class TestOneProcessPerChip:
    def test_fleet_workers_start_pinned_to_the_cpu(self, monkeypatch,
                                                   tmp_path):
        import multiprocessing.context as mpc
        from repro.core.tuning.pool import WorkerPool
        seen = []
        monkeypatch.setattr(mpc.SpawnProcess, "start", lambda self: seen
                            .append(os.environ.get("JAX_PLATFORMS")))
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        WorkerPool(2, tmp_path)
        assert seen == ["cpu", "cpu"]
        assert os.environ["JAX_PLATFORMS"] == "tpu"   # parent untouched


class TestChipSmoke:
    def test_refuses_the_cpu_and_prints_no_result(self):
        r = _run_smoke(ROOT / "chip_smoke.py", ROOT)
        assert r.returncode != 0
        assert "'cpu'" in r.stderr
        assert '"ok"' not in r.stdout

    def test_fails_without_the_repository(self, tmp_path):
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        r = _run_smoke(tmp_path / "chip_smoke.py", tmp_path)
        assert r.returncode != 0
        assert '"ok"' not in r.stdout

    def test_whole_check_passes_interpreted_at_reduced_size(self):
        smoke = _smoke_module()
        res = smoke.serve_and_check(SMALL, seed=0, n_requests=5,
                                    log=lambda *a: None)
        assert res["decode_ticks"] == res["counters"]["kernel_decode_ticks"]
        assert res["prefill_ticks"] >= 4      # the long prompt's chunks
        # each request's first token comes from its last prefill chunk
        assert res["reference"]["positions"] == \
            res["counters"]["decode_tokens"] + 5
        assert res["reference"]["max_gap_sd"] <= smoke.GAP_TOL
        # the CPU interprets the kernels: no Mosaic custom call
        assert "decode" in res["steps"]
        assert not any(s["tpu_custom_call"] for s in res["steps"].values())

    def test_reference_check_catches_a_leaking_mask(self, monkeypatch):
        """The float32 reference has teeth: with the segment test gone
        from the ragged-prefill mask, prompts attend across sequences
        and the check fails."""
        from repro.kernels.ragged_prefill import ragged_prefill as rp
        smoke = _smoke_module()
        real = rp._ragged_kernel

        def leaking(q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref, pk_ref,
                    *rest, **kw):
            # every token looks like segment 0
            sq_ref[...] = jax.numpy.where(sq_ref[...] >= 0, 0, -1)
            sk_ref[...] = jax.numpy.where(sk_ref[...] >= 0, 0, -1)
            return real(q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref, pk_ref,
                        *rest, **kw)

        monkeypatch.setattr(rp, "_ragged_kernel", leaking)
        jax.clear_caches()              # no trace of the real kernel
        try:
            with pytest.raises(smoke.SmokeFailure,
                               match="below the reference"):
                smoke.serve_and_check(SMALL, seed=0, n_requests=5,
                                      log=lambda *a: None)
        finally:
            jax.clear_caches()
