"""The reduction from a trace to busy time, idle gaps and per-call device
time: on a hand-made trace with known answers, and on a trace recorded on
a TPU v5e (``testdata/trace_v5e.json``: the device operations and call
spans of one run of ``qwen2-0.5b.ppo-b8-p128-g512``, cut to a few hundred
events around the boundaries between calls)."""

from pathlib import Path

import pytest

from chipbench import trace as TR

RECORDED = Path(__file__).resolve().parent / "testdata" / "trace_v5e.json"


def hand_made() -> TR.Events:
    # ns; ops on one device: [0,10) and [5,20) overlap, [40,50), [70,100)
    ops = [(0, 10, "fusion.1", 0), (5, 20, "fusion.2", 0),
           (40, 50, "fusion.1", 0), (70, 100, "custom-call.3", 0)]
    spans = [(0, 30, "actor_gen"), (35, 60, "ref_inf"),
             (65, 100, "actor_train")]
    return TR.Events(ops, spans, 1)


def test_hand_made_trace():
    s = TR.reduce(hand_made())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(60e-9)  # 20 + 10 + 30
    assert s.call_device_s == pytest.approx(
        {"actor_gen": 20e-9, "ref_inf": 10e-9, "actor_train": 30e-9})
    assert s.call_span_s == pytest.approx(
        {"actor_gen": 30e-9, "ref_inf": 25e-9, "actor_train": 35e-9})
    # gaps [20,40) and [50,70), equally long, so the earlier first: the
    # middle of the first (30) is where actor_gen's span ends, the middle
    # of the second (60) where ref_inf's ends
    assert s.idle_gaps == [["after actor_gen", pytest.approx(20e-9)],
                           ["after ref_inf", pytest.approx(20e-9)]]
    assert s.device_ops[0] == ["custom-call.3", pytest.approx(30e-9)]
    assert s.device_ops[1] == ["fusion.1", pytest.approx(20e-9)]


def test_ops_outside_the_window_are_clipped():
    ev = hand_made()
    ev.ops.append((90, 130, "fusion.9", 0))
    s = TR.reduce(ev)
    assert s.busy_s == pytest.approx(60e-9)
    assert ["fusion.9", pytest.approx(10e-9)] in s.device_ops


def test_devices_are_averaged():
    ev = hand_made()
    ev.ops += [(0, 100, "fusion.1", 1)]
    ev.devices = 2
    assert TR.reduce(ev).busy_s == pytest.approx(80e-9)


def _sweep(ops, t0, t1):
    """Covered time and idle runs of device 0 in [t0, t1), by a sweep over
    +1/-1 boundary events (independent of ``trace.merge``)."""
    inside = [(s, e) for s, e, _, d in ops if d == 0 and e > t0 and s < t1]
    edges = sorted([(max(s, t0), 1) for s, _ in inside]
                   + [(min(e, t1), -1) for _, e in inside])
    covered, idle, depth, last = 0, [], 0, t0
    for t, step in edges:
        if depth > 0:
            covered += t - last
        elif t > last:
            idle.append(t - last)
        depth += step
        last = t
    if t1 > last:
        idle.append(t1 - last)
    return covered, idle


@pytest.fixture(scope="module")
def recorded():
    return TR.Events.load(RECORDED)


def test_recorded_trace_is_a_real_cut(recorded):
    assert 100 <= len(recorded.ops) <= 1000
    assert {n for _, _, n in recorded.spans} >= {"actor_gen", "actor_train"}


def test_recorded_busy_and_calls_match_a_sweep(recorded):
    s = TR.reduce(recorded)
    t0 = min(a for a, _, _ in recorded.spans)
    t1 = max(b for _, b, _ in recorded.spans)
    assert s.window_s == pytest.approx((t1 - t0) * 1e-9)
    assert s.busy_s == pytest.approx(_sweep(recorded.ops, t0, t1)[0] * 1e-9,
                                     rel=1e-12)
    for name in s.call_device_s:
        want = sum(_sweep(recorded.ops, a, b)[0]
                   for a, b, n in recorded.spans if n == name)
        assert s.call_device_s[name] == pytest.approx(want * 1e-9, rel=1e-12)
    assert set(s.call_device_s) == {n for _, _, n in recorded.spans}


def test_recorded_idle_gaps(recorded):
    s = TR.reduce(recorded)
    gaps = [g for _, g in s.idle_gaps]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) == 10
    assert sum(gaps) <= s.window_s - s.busy_s + 1e-12
    t0 = min(a for a, _, _ in recorded.spans)
    t1 = max(b for _, b, _ in recorded.spans)
    runs = sorted((g * 1e-9 for g in _sweep(recorded.ops, t0, t1)[1]),
                  reverse=True)
    assert gaps == pytest.approx(runs[:len(gaps)])
    for where, _ in s.idle_gaps:
        assert where.startswith(("in ", "after ", "start of window"))
