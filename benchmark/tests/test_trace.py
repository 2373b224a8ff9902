"""The trace reduction, on a synthetic trace shaped like the profiler's
and on a small one recorded here."""
from types import SimpleNamespace as NS

import pytest

from benchmark.tests import toy  # noqa: F401
from benchmark.harness import trace
from benchmark.harness.programs import runs_of, stage_program
from benchmark.harness.common import RunData

MS = 1_000_000


def _event(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def _profile():
    """1000 ms window.  Device: a 100 ms `jit_fused` (program 1) at 100,
    400, 700; a 20 ms `jit_fused` (program 2) right after each; a
    `jit_paged_decode_step` of two 5 ms operations at 900.  Host: the
    harness waits at 0-100 and reads back during 230-400."""
    ops, modules = [], []
    for start in (100, 400, 700):
        ops.append(_event("%fusion.320 = bf16[8]{0} fusion(...)", start, 60))
        ops.append(_event("%fusion.7 = bf16[8]{0} fusion(...)",
                          start + 60, 40))
        ops.append(_event("%convolution.2 = f32[4]{0} convolution(...)",
                          start + 100, 20))
        modules.append(_event("jit_fused(111)", start, 100, run_id=start))
        modules.append(_event("jit_fused(222)", start + 100, 20))
    ops.append(_event("%fusion.1 = s32[] fusion()", 900, 5))
    ops.append(_event("%fusion.2 = s32[] fusion()", 905, 5))
    modules.append(_event("jit_paged_decode_step(333)", 900, 10))
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops),
        NS(name="Steps", events=[_event("0", 0, 1000)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _event("bench:trace_window", 0, 1000),
        _event("bench:wait_next_arrival", 0, 100),
        _event("bench:readback", 230, 170),
        _event("PjitFunction(fused)", 95, 2)]),
        NS(name="python3", events=[
            _event("np.asarray(jax.Array)", 520, 180)])])
    return NS(planes=[host, device])


def test_busy_idle_and_programs():
    reduced = trace.reduce(_profile())
    assert reduced["window_s"] == pytest.approx(1.0)
    assert reduced["busy_s"] == pytest.approx(0.37)
    programs = reduced["programs"]
    assert set(programs) == {"jit_fused#1", "jit_fused#2",
                             "jit_paged_decode_step"}
    assert programs["jit_fused#1"]["seconds"] == pytest.approx(0.3)
    assert programs["jit_fused#1"]["run_seconds"] == pytest.approx(
        [0.1, 0.1, 0.1])
    assert programs["jit_fused#2"]["seconds"] == pytest.approx(0.06)
    assert runs_of(reduced, "jit_paged_decode_step") == pytest.approx(
        [0.01])


def test_breakdown_names_programs_and_harness_spans():
    breakdown = trace.reduce(_profile())["breakdown"]
    names = [name for name, _ in breakdown["device_ops"]]
    assert names[:3] == ["jit_fused#1", "jit_fused#2",
                         "jit_paged_decode_step"]
    assert len(breakdown["device_ops"]) <= 10
    gaps = dict(breakdown["idle_gaps"])
    assert gaps["bench:wait_next_arrival"] == pytest.approx(0.1)
    assert gaps["bench:readback"] == pytest.approx(0.18)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(0.18)
    assert gaps["no_host_span"] == pytest.approx(0.17)
    assert sum(gaps.values()) == pytest.approx(1.0 - 0.37)
    ops = dict(breakdown["device_ops"])
    assert ops["jit_fused#1/fusion.320"] == pytest.approx(0.18)


def test_stage_programs_are_taken_in_the_drivers_order():
    run = RunData(trace=trace.reduce(_profile()),
                  stage_order=("lm", "asr", "detector"))
    assert stage_program(run, "lm")["seconds"] == pytest.approx(0.3)
    assert stage_program(run, "asr")["seconds"] == pytest.approx(0.06)
    assert stage_program(run, "detector") is None


def test_executions_cut_by_the_window_are_not_whole():
    profile = _profile()
    profile.planes[0].lines[0].events[0] = _event(
        "bench:trace_window", 150, 600)
    reduced = trace.reduce(profile)
    assert reduced["programs"]["jit_fused#1"]["run_seconds"] == \
        pytest.approx([0.1])


def test_a_recorded_trace(tmp_path):
    """Record a few steps here and reduce them: the program is found by
    its jitted name, and the harness's span names the idle time."""
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def rehearsal_step(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((128, 128))
    rehearsal_step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:trace_window"):
        for _ in range(3):
            rehearsal_step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:wait_next_arrival"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    reduced = trace.reduce(trace.load(str(path)))
    assert "jit_rehearsal_step" in reduced["programs"]
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert gaps.get("bench:wait_next_arrival", 0) > 0.04
