"""Whole runs of the three tiny mixes on the CPU, untraced and traced
(the traced run's device plane is a stand-in: the CPU has none), checked
against the output contract: the last line of standard output is one
JSON object with the keys the contract names, the metrics are the cell's
own, and every number compared comes with its limit."""

import contextlib
import io
import json
import pathlib

import pytest

from bench import costs, run
from bench import trace as tracemod

DATA = pathlib.Path(__file__).resolve().parent / "data"
SPEC = json.loads((DATA / "BENCHMARK.json").read_text())


@pytest.fixture(autouse=True)
def _cpu(monkeypatch):
    monkeypatch.setattr(costs, "peaks", lambda kind: {
        "bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9})

    def load(_dir):
        # one op covering the whole window: the window is all busy
        return {"devices": {"/device:TPU:0": {
            "ops": [("op", 0.0, 1e9)],
            "modules": [("jit_decode_sample_paged", 0.0, 1e9)]}},
            "host": [("bench.window", 0.0, 1.0)]}
    monkeypatch.setattr(tracemod, "load", load)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.sessions", "tiny.docs"])
def test_a_run_prints_the_contracted_line(cell, trace, capsys):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert run.main(["--workload", cell, "--seed", str(2**33 + 9),
                         "--seconds", "2", "--trace", str(trace)],
                        bench_file=DATA / "BENCHMARK.json", data_dir=DATA,
                        platform="cpu") == 0
    res = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in run._metrics_for(SPEC, cell, bool(trace))}
    assert set(res["metrics"]) <= want
    assert res["device"]["platform"] == "cpu"
    if trace:
        assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        if cell == "tiny.sessions":
            assert res["metrics"]["kv_pool.prefix_hit_share"]["value"] > 0.3
    else:
        assert want == set(res["metrics"])
    for v in res["checks"].values():
        assert set(v) == {"value", "limit"}
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("[bench cpu:cpu x1] check ")
