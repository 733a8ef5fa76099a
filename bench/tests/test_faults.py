"""A run with the timed path broken underneath reads ``correct: false``.

Each test drives a whole run of a small cell on the CPU (the harness's
look for a chip is pointed at the CPU) with one fault planted in the
engine's decode program: the step returns its state unchanged, half of
the batch is left out, or a token is altered where it is produced.  The
cell is one chip, so there is no exchange between chips to leave out.
The control test runs the program's own int8 path (``quant_serving``)
in the program's place; it too must fail the cell's check.
"""

import contextlib
import io
import json
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import costs, run

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "small.chat"
SEEDS = (31, 32, 33)


@pytest.fixture(autouse=True)
def _cpu_peaks(monkeypatch):
    monkeypatch.setattr(costs, "peaks", lambda kind: {
        "bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9})


def _run(seed, cell=CELL, trace=0):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                  "--trace", str(trace)],
                 bench_file=DATA / "BENCHMARK.json", data_dir=DATA,
                 platform="cpu")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _plant(monkeypatch, wrap):
    """Wrap the engine's paged decode program with ``wrap``."""
    from repro.serving import engine as E
    orig = E._engine_fns

    def fns(cfg, max_len):
        f = dict(orig(cfg, max_len))
        f["decode_sample_paged"] = wrap(f["decode_sample_paged"])
        return f

    monkeypatch.setattr(E, "_engine_fns", fns)


def _state_unchanged(dec):
    def f(params, toks, caches, *a):
        tok, _, key = dec(params, toks, caches, *a)
        return tok, caches, key
    return f


def _half_batch(dec):
    def f(params, toks, caches, *a):
        tok, caches, key = dec(params, toks, caches, *a)
        rows = jnp.arange(tok.shape[0])
        return jnp.where(rows < tok.shape[0] // 2, tok, toks[:, 0]), \
            caches, key
    return f


def _token_altered(dec):
    def f(params, toks, caches, *a):
        tok, caches, key = dec(params, toks, caches, *a)
        return (tok + 1) % params["embed"]["table"].shape[0], caches, key
    return f


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_runs_are_correct(seed):
    res = _run(seed)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    res = _run(SEEDS[0])
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_int8_control_is_not_correct(monkeypatch, seed):
    import dataclasses

    from bench.models import qwen2
    orig = qwen2.serving_config
    monkeypatch.setattr(qwen2, "serving_config", lambda cj, dt: dataclasses
                        .replace(orig(cj, dt), quant_serving=True))
    orig_load = run._load_module
    monkeypatch.setattr(run, "_load_module", lambda p, n: qwen2 if n ==
                        "bench_family_qwen2" else orig_load(p, n))
    res = _run(seed)
    assert not res["correct"], res["checks"]


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    root = pathlib.Path(run.__file__).resolve().parents[1]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "cpu" in p.stderr


def test_a_cell_is_added_by_files_and_one_entry(tmp_path):
    """A new mix, a new cell file and one BENCHMARK entry: no code."""
    import shutil
    data = tmp_path / "data"
    shutil.copytree(DATA, data)
    mix = json.loads((data / "mixes" / "tiny-chat.json").read_text())
    mix["output"] = {"dist": "uniform", "min": 3, "max": 6}
    (data / "mixes" / "tiny-short.json").write_text(json.dumps(mix))
    shutil.copy(data / "cells" / "tiny.chat.json",
                data / "cells" / "tiny.short.json")
    spec = json.loads((data / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.short", "config": "tiny-qwen2",
                              "traffic": "tiny-short", "chips": 1,
                              "why": "added by data alone"})
    (data / "BENCHMARK.json").write_text(json.dumps(spec))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        run.main(["--workload", "tiny.short", "--seed", "5", "--seconds",
                  "1", "--trace", "0"], bench_file=data / "BENCHMARK.json",
                 data_dir=data, platform="cpu")
    res = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tpot_p95_ms", "setup_s"}
