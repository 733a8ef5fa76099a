"""The readers of the engine's spans inside ``step()``
(``engine.itl_p99_ms``, ``engine.step_self_ms``,
``kv_pool.host_ms_per_admit``) on hand-made events with known answers, and
on events of a program without those spans, where each returns None."""

import importlib.util
import pathlib
from types import SimpleNamespace

import pytest

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _E:
    def __init__(self, etype, ts, dur=0.0, rid=-1, tick=-1, **args):
        self.etype, self.ts, self.dur, self.rid, self.tick, self.args = \
            etype, ts, dur, rid, tick, args


def _run(events, rids=(1, 2), window=(0.0, 10.0)):
    return SimpleNamespace(
        events=events,
        counted=[SimpleNamespace(req=SimpleNamespace(rid=r)) for r in rids],
        window=SimpleNamespace(start=window[0], end=window[1]))


def _served():
    """Two requests: rid 1 gets tokens at 1.0 (first), 1.2, 1.4 and 2.0;
    rid 2 at 1.3 (first) and 1.4; rid 3 is not the window's."""
    return [
        _E("step", 0.9, 0.35, tick=1),
        _E("pool_admit", 0.91, 0.002, rid=1, tick=1),
        _E("admit", 0.912, rid=1, tick=1, prompt_len=8, shared_tokens=0),
        _E("pool_admit", 0.92, 0.001, rid=3, tick=1),
        _E("admit", 0.921, rid=3, tick=1, prompt_len=8, shared_tokens=0),
        _E("sync", 0.95, 0.04, tick=1),
        _E("first_token", 1.0, rid=1, tick=1),
        _E("pool_register", 1.01, 0.003, rid=1, tick=1),
        _E("decode", 1.1, 0.1, tick=1, rows=1, rids=[1], ctx=[9]),
        _E("sync", 1.15, 0.05, tick=1),
        _E("step", 1.25, 0.2, tick=2),
        _E("pool_admit", 1.26, 0.004, rid=2, tick=2),
        _E("admit", 1.265, rid=2, tick=2, prompt_len=4, shared_tokens=0),
        _E("first_token", 1.3, rid=2, tick=2),
        _E("decode", 1.32, 0.08, tick=2, rows=3, rids=[1, 2, 3],
           ctx=[10, 5, 9]),
        _E("sync", 1.35, 0.05, tick=2),
        _E("pool_release", 1.41, 0.001, rid=2, tick=2),
        _E("step", 1.9, 0.1, tick=3),
        _E("decode", 1.95, 0.05, tick=3, rows=1, rids=[1], ctx=[11]),
        _E("sync", 1.96, 0.04, tick=3),
        _E("pool_release", 2.01, 0.002, rid=1, tick=3),
        _E("step", 20.0, 0.1, tick=4),            # after the window
    ]


def test_itl_p99_reads_every_gap_of_the_window_requests():
    # tokens of rid 1 at 1.0, 1.2, 1.4, 2.0 and of rid 2 at 1.3, 1.4:
    # gaps 0.2, 0.2, 0.6 and 0.1 s
    read = _reader("engine.itl_p99_ms")
    gaps = sorted([200.0, 200.0, 600.0, 100.0])
    k = 0.99 * (len(gaps) - 1)
    want = gaps[2] + (gaps[3] - gaps[2]) * (k - 2)
    assert read(_run(_served())) == pytest.approx(want)
    # rid 2 alone: one gap, 0.1 s
    assert read(_run(_served(), rids=(2,))) == pytest.approx(100.0)


def test_step_self_is_each_step_less_its_syncs():
    s = [0.35 - 0.09, 0.2 - 0.05, 0.1 - 0.04]
    read = _reader("engine.step_self_ms")
    assert read(_run(_served())) == pytest.approx(1e3 * sum(s) / 3)
    # the window holds the second step only
    assert read(_run(_served(), window=(1.2, 1.5))) == \
        pytest.approx(1e3 * s[1])


def test_pool_host_time_per_admission_of_the_window_requests():
    got = _reader("kv_pool.host_ms_per_admit")(_run(_served()))
    assert got == pytest.approx(
        1e3 * (0.002 + 0.003 + 0.004 + 0.001 + 0.002) / 2)


@pytest.mark.parametrize("name", ["engine.itl_p99_ms",
                                  "engine.step_self_ms",
                                  "kv_pool.host_ms_per_admit"])
def test_a_program_without_the_spans_reads_none(name):
    """Lifecycle events alone, as a tracer without step spans records
    them (no ``tick``, no ``rids`` on ``decode``)."""
    old = [SimpleNamespace(etype=e.etype, ts=e.ts, dur=e.dur, rid=e.rid,
                           args={k: v for k, v in e.args.items()
                                 if k not in ("rids", "ctx")})
           for e in _served() if e.etype in
           ("admit", "first_token", "decode", "finish")]
    read = _reader(name)
    assert read(_run(old)) is None
    assert read(_run(None)) is None
