"""The program's spans and the device's module events, read beside the
benchmark's own reduction: on a hand-made trace with known answers, on a
stand-in for a profile, and on a trace recorded on a TPU v5e
(``testdata/trace_v5e_spans.json``: one run of
``qwen2-0.5b.ppo-b8-p128-g512`` cut by ``spans.Program.cut``).  The six
per-layer metrics the benchmark reports are pinned on its own recorded trace
(``testdata/trace_v5e.json``) at the values they read before the program
had spans."""

import json
import types
from pathlib import Path

import pytest

from chipbench import cell as C
from chipbench import run as R
from chipbench import spans as S
from chipbench import trace as TR

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "testdata" / "trace_v5e_spans.json"
BENCH_RECORDED = HERE / "testdata" / "trace_v5e.json"
CELL = "qwen2-0.5b.ppo-b8-p128-g512"


def hand_made():
    # ns; device 0 busy [0,20) [40,50) [70,100) [120,130): 70 of 150
    ops = [(0, 10, "%fusion.1", 0), (5, 20, "%flash_decode.4", 0),
           (40, 50, "%fusion.1", 0), (70, 100, "%custom-call.3", 0),
           (120, 130, "%flash_mha.2", 0)]
    calls = [(0, 30, "actor_gen"), (35, 60, "actor_train"),
             (65, 100, "critic_train"), (115, 140, "actor_gen")]
    program = [
        (0, 100, "rt.iteration", {"step_num": 0}),
        (110, 150, "rt.iteration", {"step_num": 1}),
        (0, 30, "rt.exec", {"call": "actor_gen", "iteration": 0}),
        (35, 60, "rt.exec", {"call": "actor_train", "iteration": 0}),
        (65, 100, "rt.exec", {"call": "critic_train", "iteration": 0}),
        (115, 140, "rt.exec", {"call": "actor_gen", "iteration": 1}),
        (35, 45, "ppo.adv", {"model": "actor"}),
        (65, 75, "ppo.adv", {"model": "critic"}),
    ]
    modules = [(0, 25, "jit_actor_generate(11)", 0),
               (38, 60, "jit_actor_train_step(12)", 0),
               (68, 100, "jit_critic_train_step(13)", 0),
               (118, 135, "jit_actor_generate(11)", 0)]
    return TR.Events(ops, calls, 1), S.Program(program, modules)


def test_hand_made_breakdown():
    b = S.reduce(*hand_made())
    assert b.iterations == 2
    assert b.window_s == pytest.approx(150e-9)
    # outside the executor spans: [30,35) [60,65) [100,115) [140,150)
    assert b.idle_outside_s == pytest.approx(35e-9)
    assert b.idle_in_span_s["ppo.adv"] == pytest.approx(10e-9)
    assert b.idle_in_span_s["rt.exec"] == pytest.approx(45e-9)
    assert b.idle_in_span_s["rt.exec/actor_train"] == pytest.approx(15e-9)
    assert b.idle_in_span_s["rt.exec/actor_gen"] == pytest.approx(25e-9)
    # [100,110), between the iterations, lies in neither
    assert b.idle_in_span_s["rt.iteration"] == pytest.approx(70e-9)
    assert b.module_device_s == pytest.approx({
        "jit_actor_generate": 30e-9, "jit_actor_train_step": 10e-9,
        "jit_critic_train_step": 30e-9})
    assert b.kernel_device_s == pytest.approx({"flash_decode": 15e-9,
                                               "flash_mha": 10e-9})


def test_hand_made_readings():
    counters = {"actor_train": {"traces": 26, "lowerings": 2,
                                "compile_s": 0.1},
                "critic_train": {"traces": 26, "lowerings": 2,
                                 "compile_s": 0.1},
                "actor_gen": {"traces": 2, "lowerings": 0, "compile_s": 0.0},
                "(outside)": {"traces": 0, "lowerings": 0, "compile_s": 0.0}}
    got = S.readings(S.reduce(*hand_made()), counters)
    assert got == pytest.approx({
        "device_ms.actor_gen": 1.5e-5, "idle_ms.adv": 5e-6,
        "idle_ms.runtime": 1.75e-5, "compiles_per_iter": 2.0,
        "device_ms.flash_decode": 7.5e-6})


def test_readings_leave_out_what_the_trace_lacks():
    ev, prog = hand_made()
    prog = S.Program([s for s in prog.spans if s[2] != "ppo.adv"], [])
    ev.ops = [o for o in ev.ops if "flash" not in o[2]]
    got = S.readings(S.reduce(ev, prog))
    assert set(got) == {"idle_ms.runtime"}
    with pytest.raises(ValueError):
        S.reduce(ev, S.Program([], []))


def test_counter_delta():
    before = {"actor_train": {"count": 3, "lowerings": 4, "traces": 9,
                              "compile_s": 1.0},
              "(outside)": {"lowerings": 1, "traces": 5, "compile_s": 0.5}}
    after = {"actor_train": {"count": 6, "lowerings": 7, "traces": 20,
                             "compile_s": 1.5},
             "ref_inf": {"count": 1, "lowerings": 0, "traces": 0,
                         "compile_s": 0.0},
             "(outside)": {"lowerings": 1, "traces": 6, "compile_s": 0.5}}
    assert S.counter_delta(before, after) == {
        "actor_train": {"traces": 11, "lowerings": 3, "compile_s": 0.5},
        "ref_inf": {"traces": 0, "lowerings": 0, "compile_s": 0.0},
        "(outside)": {"traces": 1, "lowerings": 0, "compile_s": 0.0}}


def _profile(planes):
    """A stand-in for ``jax.profiler.ProfileData``: planes of lines of
    events with the attributes the readers use."""
    ev = lambda s, e, n, **st: types.SimpleNamespace(  # noqa: E731
        start_ns=float(s), end_ns=float(e), name=n, stats=list(st.items()))
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p, lines=[
            types.SimpleNamespace(name=ln, events=[ev(*x[:3], **x[3])
                                                   for x in events])
            for ln, events in lines.items()])
        for p, lines in planes.items()])


def test_program_spans_stay_out_of_the_benchmark_spans():
    prof = _profile({
        "/host:CPU": {
            "python": [(0, 50, "call:actor_gen", {}),
                       (0, 60, "rt.exec", {"call": "actor_gen",
                                           "iteration": 0}),
                       (1, 2, "$pjit.py:250 cache_miss", {})],
            "loop": [(0, 70, "rt.iteration", {"step_num": 0, "_r": 1}),
                     (60, 65, "ppo.adv", {"model": "actor"})]},
        "/device:TPU:0": {
            "XLA Ops": [(5, 40, "%fusion.1 = f32[8] fusion(...)", {})],
            "XLA Modules": [(4, 41, "jit_actor_generate(7)", {})]}})
    ev = TR.from_profile(prof)
    assert ev.spans == [(0, 50, "actor_gen")]
    assert ev.ops == [(5, 40, "%fusion.1", 0)]
    prog = S.read(prof)
    assert [(s[2], s[3]) for s in prog.spans] == [
        ("rt.exec", {"call": "actor_gen", "iteration": 0}),
        ("rt.iteration", {"step_num": 0, "_r": 1}),
        ("ppo.adv", {"model": "actor"})]
    assert prog.modules == [(4, 41, "jit_actor_generate(7)", 0)]
    assert not [s for s in prog.spans if s[2].startswith(TR.SPAN_PREFIX)]


def test_names():
    assert S.module_name("jit_actor_generate(123)") == "jit_actor_generate"
    assert S.module_name("jit_critic_train_step") == "jit_critic_train_step"
    assert S.op_kernel("%flash_decode.8") == "flash_decode"
    assert S.op_kernel("paged_flash_decode") == "paged_flash_decode"
    assert S.op_kernel("%while.50") == "while"


# ------------------------------------------ the benchmark's six, pinned

PINNED = {"mfu": 24.57553737727104, "call_ms.actor_gen": 5.509328000000001,
          "call_ms.train": 761.2094840000001,
          "actor_gen_roofline": 462392.47604825214,
          "train_roofline": 3214.4152289293575,
          "idle_share": 86.70362077747689}


def test_existing_metrics_read_as_before():
    """One iteration's worth of the recorded cut, read by each reader."""
    cell = C.load_cell(CELL)
    with open(HERE / "peaks.json") as f:
        peak = json.load(f)["TPU v5 lite"]
    ctx = types.SimpleNamespace(
        trace=TR.reduce(TR.Events.load(BENCH_RECORDED)), iterations=1,
        chips=1, peak=peak,
        costs=cell.costs)
    got = {m["name"]: R.load_metric(m["name"]).read(ctx)
           for m in cell.per_layer}
    assert got == pytest.approx(PINNED, rel=1e-12)


# ------------------------------------------------ the recorded v5e trace

@pytest.fixture(scope="module")
def recorded():
    return S.Program.load(RECORDED)


def _covered(ops, t0, t1) -> int:
    """ns of [t0, t1) in which device 0 runs an operation, by a sweep over
    +1/-1 boundary events (independent of ``trace.merge``)."""
    edges = sorted([(max(s, t0), 1) for s, e, _, d in ops
                    if d == 0 and e > t0 and s < t1]
                   + [(min(e, t1), -1) for s, e, _, d in ops
                      if d == 0 and e > t0 and s < t1])
    covered, depth, last = 0, 0, t0
    for t, step in edges:
        if depth > 0:
            covered += t - last
        depth += step
        last = t
    return covered


def test_recorded_trace_is_a_real_cut(recorded):
    ev, prog = recorded
    assert 100 <= len(ev.ops) <= 1000
    names = {s[2] for s in prog.spans}
    assert names >= {"rt.iteration", "rt.wait", "rt.realloc", "rt.exec",
                     "rt.retire", "ppo.adv", "ppo.step", "ppo.sync"}
    assert {s[3]["call"] for s in prog.spans if s[2] == "rt.exec"} >= {
        "actor_gen", "reward_inf", "ref_inf", "critic_inf", "actor_train",
        "critic_train"}
    assert {S.module_name(m[2]) for m in prog.modules} >= {
        "jit_actor_generate", "jit_ref_logprobs", "jit_reward_scores",
        "jit_critic_values", "jit_actor_train_step", "jit_critic_train_step"}
    assert {S.op_kernel(o[2]) for o in ev.ops} >= {"flash_decode",
                                                   "flash_mha"}
    # the benchmark's blocking call spans run inside the executor spans
    execs = [(s[0], s[1], s[3]["call"]) for s in prog.spans
             if s[2] == "rt.exec"]
    for s, e, name in ev.spans:
        assert any(a <= s and e <= b and c == name for a, b, c in execs)


def test_recorded_breakdown_matches_a_sweep(recorded):
    ev, prog = recorded
    b = S.reduce(ev, prog)
    its = [s for s in prog.spans if s[2] == "rt.iteration"]
    t0, t1 = min(s[0] for s in its), max(s[1] for s in its)
    assert b.window_s == pytest.approx((t1 - t0) * 1e-9)
    adv = [s for s in prog.spans if s[2] == "ppo.adv"]
    want = sum((e - s) - _covered(ev.ops, s, e) for s, e, _, _ in adv)
    assert b.idle_in_span_s["ppo.adv"] == pytest.approx(want * 1e-9,
                                                        rel=1e-12)
    # outside the executors = window idle - idle inside the executors
    execs = TR.merge([(s[0], s[1]) for s in prog.spans if s[2] == "rt.exec"])
    idle_in = sum((e - s) - _covered(ev.ops, s, e) for s, e in execs)
    idle_all = (t1 - t0) - _covered(ev.ops, t0, t1)
    assert b.idle_outside_s == pytest.approx((idle_all - idle_in) * 1e-9,
                                             rel=1e-12)
    gen = [m for m in prog.modules
           if S.module_name(m[2]) == "jit_actor_generate"]
    want = sum(_covered(ev.ops, max(s, t0), min(e, t1))
               for s, e, _, _ in gen if e > t0 and s < t1)
    assert b.module_device_s["jit_actor_generate"] == pytest.approx(
        want * 1e-9, rel=1e-12)
    flash = sum(min(e, t1) - max(s, t0) for s, e, n, _ in ev.ops
                if S.op_kernel(n) == "flash_decode" and e > t0 and s < t1)
    assert b.kernel_device_s["flash_decode"] == pytest.approx(flash * 1e-9)


def test_recorded_readings(recorded):
    got = S.readings(S.reduce(*recorded))
    assert set(got) == {"device_ms.actor_gen", "idle_ms.adv",
                        "idle_ms.runtime", "device_ms.flash_decode"}
    assert all(v > 0 for v in got.values())
