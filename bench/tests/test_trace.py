"""The trace reduction (bench/trace.py) and the decode-step rebuild
(bench/steps.py), on a hand-made trace with known answers and on a small
trace recorded on a TPU v5e (``data/trace_v5e.json``: the events of a
short traced window of ``qwen2-0.5b.chat``, as ``bench.trace.load``
returned them)."""

import json
import pathlib

import pytest

from bench import trace
from bench.steps import decode_contexts

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _made():
    return {
        "devices": {"/device:TPU:0": {
            "ops": [("fusion.1", 1.0, 0.5), ("paged_decode", 1.2, 0.4),
                    ("fusion.2", 3.0, 1.0), ("outside", 20.0, 1.0)],
            "modules": [("jit_decode_sample_paged(1)", 1.0, 0.7),
                        ("jit_prefill_chunk(2)", 3.0, 1.0)]}},
        "host": [("bench.window", 0.5, 9.5), ("bench.step", 0.6, 1.3),
                 ("bench.step", 2.8, 1.4), ("bench.wait", 5.0, 4.0)],
    }


def test_busy_is_the_union_of_op_intervals_in_the_window():
    red = trace.reduce(_made())
    assert red["window_s"] == pytest.approx(9.5)
    assert red["busy_s"] == pytest.approx((1.6 - 1.0) + (4.0 - 3.0))
    assert red["ops"]["paged_decode"] == pytest.approx(0.4)
    assert "outside" not in red["ops"]
    assert trace.modules_matching(red, "decode_sample_paged") == \
        pytest.approx((0.7, 1))


def test_idle_gaps_are_put_down_to_the_host_span():
    red = trace.reduce(_made())
    gaps = [(k, round(d, 6)) for k, d in red["idle_gaps"]]
    assert gaps == [("host: bench.wait", 6.0),     # 4.0 .. 10.0
                    ("host: none", 1.4),           # 1.6 .. 3.0
                    ("host: bench.step", 0.5)]     # 0.5 .. 1.0
    # host time of each step: its span less the device busy inside it
    assert red["step_host_s"] == pytest.approx([1.3 - 0.6, 1.4 - 1.0])


def test_breakdown_lists_the_top_ops_and_gaps():
    b = trace.breakdown(trace.reduce(_made()), n=2)
    assert [k for k, _ in b["device_ops"]] == ["fusion.2", "fusion.1"]
    assert len(b["idle_gaps"]) == 2


class _E:
    def __init__(self, etype, ts, slot=-1, rid=-1, **args):
        self.etype, self.ts, self.slot, self.rid, self.args = \
            etype, ts, slot, rid, args


def test_decode_steps_rebuilt_from_lifecycle_events():
    ev = [_E("admit", 0.0, slot=0, rid=1, prompt_len=10, shared_tokens=0),
          _E("first_token", 1.0, slot=0, rid=1),
          _E("decode", 1.1, rows=1), _E("decode", 1.2, rows=2),
          _E("admit", 1.05, slot=1, rid=2, prompt_len=5, shared_tokens=0),
          _E("first_token", 1.15, slot=1, rid=2),
          _E("finish", 1.25, slot=0, rid=1),
          _E("decode", 1.3, rows=1)]
    assert decode_contexts(ev, 0.0, 2.0) == [[11], [12, 6], [7]]
    assert decode_contexts(ev, 1.15, 2.0) == [[12, 6], [7]]


def test_a_wrong_row_count_is_refused():
    ev = [_E("admit", 0.0, slot=0, rid=1, prompt_len=10, shared_tokens=0),
          _E("first_token", 1.0, slot=0, rid=1), _E("decode", 1.1, rows=2)]
    with pytest.raises(ValueError):
        decode_contexts(ev, 0.0, 2.0)


def test_the_recorded_v5e_trace():
    """The first 1.6 s of a traced window of qwen2-0.5b.chat at 64 slots,
    recorded on a TPU v5e: two engine steps, each a prefill-chunk program
    and a decode program with the Pallas paged-decode kernel in it."""
    tr = json.loads((DATA / "trace_v5e.json").read_text())
    red = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(1.6)
    assert 0 < red["busy_s"] <= red["window_s"]
    secs, n = trace.modules_matching(red, "decode_sample_paged")
    assert n == 2 and 0.05 < secs / n < 0.1
    secs, n = trace.modules_matching(red, "prefill_chunk")
    assert n >= 2 and secs / 2 > 0.5
    k_secs, k_n = trace.ops_matching(red, "paged_decode_kernel")
    assert k_n >= 1 and 0 < k_secs < 0.2
    assert all(label.startswith("host: ") for label, _ in red["idle_gaps"])
    assert len(red["step_host_s"]) == 2
    b = trace.breakdown(red)
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][1] > 0.1
