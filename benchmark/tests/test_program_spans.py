"""The program's spans out of a profile, and every reader that goes by
them, on a synthetic profile laid out like a TPU run's: children by
containment, closing marks as intervals, the window's edges, and None
under three samples."""
from types import SimpleNamespace as NS

import pytest

from benchmark.tests import toy  # noqa: F401
from benchmark.harness import cells, program_spans, trace
from benchmark.harness.common import RunData

MS = 1_000_000
NEW_METRICS = (
    "ingress_wait_ms.chat", "ingress_wait_ms.long", "first_chunk_ms.chat",
    "first_chunk_ms.long", "engine_tick_self_ms", "engine_pump_gap_ms",
    "decode_batch_mean", "sched_hold_idle_pct", "idle_unnamed_ms_per_s")


def _event(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def _tick(start, decoding, prefill=None):
    """One pump of 100 ms: step inside it, decode 2 ms, readback 90 ms,
    optionally a prefill of `prefill` ms first; the rest is the step's
    own time."""
    events = [_event("aiko:engine.pump", start, 100, waited_us=500),
              _event("aiko:engine.step", start + 1, 98, waiting=0,
                     active=decoding, decoding=decoding, admitted=0)]
    at = start + 2
    if prefill:
        events.append(_event("aiko:engine.prefill", at, prefill,
                             stream="r1", frame=0, row=0, bucket=64,
                             true_len=40, queue_us=100))
        at += prefill
    events.append(_event("aiko:engine.decode", at, 2, decoding=decoding))
    events.append(_event("aiko:engine.readback", at + 2, 90))
    return events


def _served_profile(requests=3):
    """1000 ms window.  Replica loop (one line): ticks at 0.5 ms gaps from
    100 on; `requests` requests, each with an ingress mark, a submit
    mark, a first chunk and a second that says the same of how the
    request began.  Gateway loop (another line): route + admit."""
    replica, gateway = [], []
    for index, start in enumerate((100, 200.5, 301, 401.5, 502)):
        replica += _tick(start, decoding=4 + index,
                         prefill=4 if index == 1 else None)
    # one tick that only admitted: no decode, not a sample of self time
    replica += [_event("aiko:engine.pump", 700, 10, waited_us=90_000),
                _event("aiko:engine.step", 701, 8, waiting=1, active=1,
                       decoding=0, admitted=1)]
    for index in range(requests):
        at = 610 + index * 10
        gateway += [
            _event("aiko:gateway.route", at - 60, 0.2, stream=f"r{index}",
                   frame=0, trace_id=f"t{index}", replica="a",
                   pool="decode"),
            _event("aiko:gateway.admit", at - 60 + 0.1, 0.001,
                   stream=f"r{index}", frame=0, trace_id=f"t{index}",
                   waited_us=80)]
        replica += [
            _event("aiko:ingress", at, 0.001, stream=f"r{index}", frame=0,
                   trace_id=f"t{index}", waited_us=60_000 + 1000 * index),
            _event("aiko:engine.submit", at + 0.2, 0.001,
                   stream=f"r{index}", frame=0, row=0,
                   trace_id=f"t{index}", waited_us=200),
            _event("aiko:engine.chunk", at + 300, 0.001,
                   stream=f"r{index}", frame=0, row=0, offset=0, tokens=8,
                   waited_us=800_000 + 10_000 * index,
                   first_us=800_000 + 10_000 * index,
                   ingress_us=60_200 + 1000 * index),
            _event("aiko:engine.chunk", at + 320, 0.001,
                   stream=f"r{index}", frame=0, row=0, offset=8, tokens=8,
                   waited_us=20_000, first_us=800_000 + 10_000 * index,
                   ingress_us=60_200 + 1000 * index)]
    # a pump cut by the window's end, and a mark after it: left out
    replica += [_event("aiko:engine.pump", 950, 100, waited_us=10),
                _event("aiko:engine.chunk", 1001, 0.001, stream="late",
                       frame=0, row=0, offset=0, tokens=8, waited_us=1)]
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[_event("bench:trace_window", 0, 1000)]),
        NS(name="python3", events=replica),
        NS(name="python3", events=gateway)])
    return NS(planes=[host])


def _graph_profile():
    """1000 ms window.  A lone frame at 300: the loop holds `asr` down for
    25 ms with the device idle, runs its group, holds `lm` down for 25 ms
    while the asr program still runs, then the lm program.  Before that the
    loop idles in 50 ms slices, the first of which the profiler missed."""
    loop = [_event("aiko:loop.idle", start, 50, loop="p")
            for start in (50, 100, 150, 200, 250)]
    loop += [
        _event("aiko:sched.hold", 300, 25, loop="p", node="asr"),
        _event("aiko:sched.group", 325, 1, node="asr", frames=1, rows=8,
               target=32, path="fused"),
        _event("aiko:sched.hold", 326, 25, loop="p", node="lm"),
        _event("aiko:sched.group", 351, 1, node="lm", frames=1, rows=8,
               target=32, path="fused")]
    loop += [_event("aiko:loop.idle", start, 50, loop="p")
             for start in range(352, 952, 50)]
    modules = [_event("jit_fused(222)", 325, 50),
               _event("jit_fused(111)", 375, 400)]
    ops = [_event("%fusion.1 = f32[8]{0} fusion(...)", 325, 50),
           _event("%fusion.2 = f32[8]{0} fusion(...)", 375, 400)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[
            _event("bench:trace_window", 0, 1000),
            _event("bench:wait_next_arrival", 10, 280)]),
        NS(name="python3", events=loop)])
    return NS(planes=[host, device])


def _run(profile, cell_name, monkeypatch, **recorded):
    """What a traced run hands its readers, with `profile` standing in
    for the file the run left."""
    spans = program_spans.parse(profile)
    monkeypatch.setattr(program_spans, "of_run", lambda run: spans)
    return RunData(recorded, cell=NS(name=cell_name),
                   trace=trace.reduce(profile))


def _read(name, run):
    return cells.load_reader(name)(run)


def test_spans_children_and_marks():
    spans = program_spans.parse(_served_profile())
    assert spans.window == (0, 1000 * MS)
    pumps = spans.named("engine.pump")
    assert len(pumps) == 6          # the one cut by the window is left out
    step = pumps[1].children[0]
    assert step.name == "engine.step" and step.parent is pumps[1]
    assert [child.name for child in step.children] == [
        "engine.prefill", "engine.decode", "engine.readback"]
    assert step.self_ns() == pytest.approx((98 - 4 - 2 - 90) * MS)
    assert {span.line for span in pumps} == {1}
    assert {span.line for span in spans.named("gateway.route")} == {2}
    # a mark nests too (the gateway writes admit inside route) ...
    admit = spans.named("gateway.admit")[0]
    assert admit.parent.name == "gateway.route"
    # ... and reaches back over the interval it closes
    ingress = spans.named("ingress")[0]
    assert ingress.interval_ns() == (550 * MS, 610 * MS)
    assert ingress.waited_ms() == 60.0
    assert pumps[0].interval_ns() == (100 * MS, 200 * MS)   # scoped
    assert not [span for span in spans.named("engine.chunk")
                if span.stats["stream"] == "late"]
    assert spans.busy is None       # no device line in this profile


def test_served_readers(monkeypatch):
    run = _run(_served_profile(), "lm.chat", monkeypatch)
    # 60/61/62 ms in the mailbox + 0.2 ms to the engine
    assert _read("ingress_wait_ms.chat", run) == pytest.approx(61.2)
    assert _read("ingress_wait_ms.long", run) == pytest.approx(61.2)
    assert _read("first_chunk_ms.chat", run) == pytest.approx(810.0)
    assert _read("first_chunk_ms.long", run) == pytest.approx(810.0)
    # step 98 less decode 2 and readback 90; the tick with a prefill of 4
    # has 2 left; the tick that decoded nothing is no sample
    assert _read("engine_tick_self_ms", run) == pytest.approx(6.0)
    # four gaps of 0.5 ms between re-posted pumps; the pump at 700 came 98
    # ms after the one before it and its message waited 90: posted after
    # that pump had ended, by a submit to an idle engine, so no sample
    assert _read("engine_pump_gap_ms", run) == pytest.approx(0.5)
    assert _read("decode_batch_mean", run) == pytest.approx(6.0)


def test_a_request_in_flight_counts_by_any_of_its_chunks(monkeypatch):
    """A window in which no request began: the requests that published a
    chunk inside it say how they began, each once; a chunk that does not
    (a resumed row, a frame that is gone) is no sample."""
    profile = _served_profile(requests=0)
    replica = profile.planes[0].lines[1]
    for index in range(3):
        for offset in (16, 24):
            replica.events.append(_event(
                "aiko:engine.chunk", 300 + offset + index, 0.001,
                stream=f"early{index}", frame=0, row=0, offset=offset,
                tokens=8, waited_us=900_000,
                first_us=(700 + 100 * index) * 1000,
                ingress_us=(30 + 10 * index) * 1000))
    replica.events.append(_event(
        "aiko:engine.chunk", 500, 0.001, stream="resumed", frame=0, row=0,
        offset=40, tokens=8, waited_us=900_000))
    run = _run(profile, "lm.longprompt", monkeypatch)
    assert _read("first_chunk_ms.long", run) == pytest.approx(800.0)
    assert _read("ingress_wait_ms.long", run) == pytest.approx(40.0)


def test_a_pump_posted_after_the_last_one_ended_is_no_gap(monkeypatch):
    """An idle engine's next pump is posted by a submit: the time since
    the pump before it is not mailbox wait."""
    profile = _served_profile()
    replica = profile.planes[0].lines[1]
    replica.events = [event for event in replica.events
                      if event.name == "aiko:engine.pump"][:4]
    replica.events[2] = _event("aiko:engine.pump", 400, 100, waited_us=20)
    run = _run(profile, "lm.chat", monkeypatch)
    # gaps: 0.5 (kept), 99.5 (waited 0.02 ms: dropped), 1.5 -> 2 samples
    assert _read("engine_pump_gap_ms", run) is None


@pytest.mark.parametrize("name", NEW_METRICS[:7])
def test_under_three_samples_reads_none(name, monkeypatch):
    """Two requests, and (for the per-tick readers) two ticks."""
    profile = _served_profile(requests=2)
    if not name.startswith(("ingress", "first_chunk")):
        replica = profile.planes[0].lines[1]
        replica.events = [event for event in replica.events
                          if event.start_ns < 301 * MS]
    run = _run(profile, "lm.chat", monkeypatch)
    assert _read(name, run) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_spans_reads_nothing(name, monkeypatch):
    """The parent of the PR that added the spans: the readers say None
    and do not raise (the unnamed idle time alone is the trace's own)."""
    profile = _graph_profile()
    profile.planes[0].lines[1].events = []
    run = _run(profile, "graph.streams", monkeypatch)
    value = _read(name, run)
    if name == "idle_unnamed_ms_per_s":
        # the harness's wait covers the middle of the gap before the
        # frame; nothing covers the gap after the lm program (775-1000)
        assert value == pytest.approx(225.0)
    else:
        assert value is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_an_untraced_run_reads_nothing(name):
    assert _read(name, RunData(cell=NS(name="lm.chat"), trace={})) is None


def test_graph_readers(monkeypatch):
    run = _run(_graph_profile(), "graph.streams", monkeypatch)
    spans = program_spans.of_run(run)
    assert spans.busy == [[325 * MS, 775 * MS]]
    holds = spans.named("sched.hold")
    assert [hold.stats["node"] for hold in holds] == ["asr", "lm"]
    # the asr hold idles the device for its 25 ms; the lm hold lies under
    # the asr program
    assert spans.idle_overlap_ns(holds[:1]) == pytest.approx(25 * MS)
    assert spans.idle_overlap_ns(holds[1:]) == pytest.approx(0)
    assert _read("sched_hold_idle_pct", run) == pytest.approx(2.5)
    gaps = dict(run.trace["breakdown"]["idle_gaps"])
    # the gap before the frame is named by its middle: the program's idle
    # slice outranks the harness's wait; the hold at its end names nothing
    assert gaps["aiko:loop.idle"] == pytest.approx(0.325 + 0.225)
    assert "bench:wait_next_arrival" not in gaps
    assert "aiko:sched.hold" not in gaps
    assert _read("idle_unnamed_ms_per_s", run) == 0.0


def test_the_profile_is_found_where_the_tracer_writes_it(tmp_path):
    directory = tmp_path / "trace-lm.chat" / "plugins" / "profile" / "x"
    directory.mkdir(parents=True)
    (directory / "host.xplane.pb").write_bytes(b"")
    assert program_spans.profile_path("lm.chat", str(tmp_path)) == str(
        directory / "host.xplane.pb")
    assert program_spans.profile_path("lm.other", str(tmp_path)) is None
    run = RunData(cell=NS(name="no.such.cell"), trace={"window_s": 1.0})
    assert program_spans.of_run(run) is None


def test_every_new_metric_is_in_the_manifest_with_a_reader():
    manifest = cells.load_manifest()
    listed = {metric["name"]: metric for metric in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert callable(cells.load_reader(name))
        assert listed[name]["workloads"], name
    assert [metric["name"] for metric in manifest["per_layer"]
            ][-len(NEW_METRICS):] == list(NEW_METRICS)


def test_a_recorded_profile(tmp_path):
    """Record the program's seam here and read it back: names, nesting
    and arguments survive the profiler."""
    import time

    import jax

    from aiko_services_tpu.observe.trace import program_mark, program_span

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:trace_window"):
        for _ in range(3):
            with program_span("engine.pump", waited_us=7):
                with program_span("engine.step", waiting=0) as step:
                    with program_span("engine.decode", decoding=2):
                        time.sleep(0.002)
                    with program_span("engine.readback"):
                        time.sleep(0.004)
                    step.set(active=2, decoding=2, admitted=0)
            program_mark("engine.chunk", 0.25, offset=0, tokens=8)
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    spans = program_spans.parse(trace.load(str(path)))
    steps = spans.named("engine.step")
    assert len(steps) == 3
    for step in steps:
        assert step.parent.name == "engine.pump"
        assert [child.name for child in step.children] == [
            "engine.decode", "engine.readback"]
        assert step.stats == {"waiting": 0, "active": 2, "decoding": 2,
                              "admitted": 0}
        assert 0 <= step.self_ns() < 2 * MS
    chunks = spans.named("engine.chunk")
    assert [chunk.waited_ms() for chunk in chunks] == [250.0] * 3
    start, stop = chunks[0].interval_ns()
    assert stop - start == pytest.approx(250 * MS)
