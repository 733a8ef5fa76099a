"""The entry points: the compile-cache helper, ``launch.serve`` failing
loudly, and ``chip_smoke.py`` rehearsed on the CPU.

``chip_smoke.py`` is rehearsed at the scaled-down qwen2-0.5b in bf16 — the
widths shrink, the dtype the chip serves in does not — with its platform
check pointed at the CPU.  Every test here that reaches an entry point
keeps the persistent compile cache in its own temporary directory and
unbinds it afterwards, so nothing lands in the checkout.
"""

import json
import os
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import configs
from repro.launch import compile_cache, serve
from repro.serving import engine as engine_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` -> a fresh directory; JAX's cache
    settings are restored and unbound afterwards."""
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    d = tmp_path / "jax_cache"
    monkeypatch.setenv(compile_cache.ENV_VAR, str(d))
    yield d
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_uses_env_dir(cache_dir):
    assert compile_cache.enable_compile_cache() == str(cache_dir)
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    assert any(cache_dir.iterdir()), "no compiled entry landed in the dir"


def test_compile_cache_default_is_fixed_in_checkout(cache_dir, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # re-pointing follows the environment again (and unbinds the default
    # before anything compiles into the checkout)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(cache_dir))
    assert compile_cache.enable_compile_cache() == str(cache_dir)


_SERVE_ARGS = ["--arch", "qwen2_0_5b", "--scaled-down", "--requests", "2",
               "--prompt-len", "12", "--max-new", "4", "--slots", "2",
               "--max-len", "32"]


def test_serve_exits_nonzero_when_every_dispatch_fails(cache_dir,
                                                       monkeypatch, capsys):
    """The model dispatches the step watchdog guards (prefill chunk,
    decode, verify) all raise, as a kernel the chip refuses would: the
    engine quarantines every request and keeps running, so the entry
    point is what must fail."""
    real = engine_mod._engine_fns

    def boom(*a, **k):
        raise RuntimeError("dispatch refused")

    def failing_fns(cfg, max_len):
        return {**real(cfg, max_len), "prefill_chunk": boom,
                "decode_sample_paged": boom, "verify_chunk": boom}

    monkeypatch.setattr(engine_mod, "_engine_fns", failing_fns)
    with pytest.raises(SystemExit) as exc:
        serve.main(_SERVE_ARGS)
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "request status: failed=2" in out
    assert "first classified error: RuntimeError" in out
    assert "dispatch refused" in out          # engine.last_dispatch_error


@pytest.fixture
def chip_smoke(cache_dir, monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as cs
    return cs


def test_chip_smoke_refuses_without_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert "FAILED" in out and '"ok"' not in out


def test_chip_smoke_phases_rehearse_on_cpu_in_bf16(chip_smoke, monkeypatch,
                                                   capsys):
    small = configs.get("qwen2_0_5b").scaled_down(
        param_dtype="bfloat16", compute_dtype="bfloat16")
    monkeypatch.setattr(configs, "get", lambda name: small)
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "SERVE", dict(
        requests=3, prompt_len=40, max_new=6, slots=2, max_len=64))
    monkeypatch.setattr(chip_smoke, "VARIANT", dict(
        requests=2, prompt_len=20, max_new=4, slots=2, max_len=64))
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for phase in ("serve", "kernels", "variants"):
        assert any(f"phase {phase}: PASS" in ln for ln in lines), phase
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert "repro.launch.dryrun" not in sys.modules
