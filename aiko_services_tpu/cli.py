# Console entry points.
#
# Capability parity with the reference's console scripts (reference:
# pyproject.toml:60-64 -- aiko_registrar, aiko_pipeline, aiko_dashboard,
# plus storage/recorder mains): one click group, `python -m
# aiko_services_tpu <command>`.

from __future__ import annotations

import click


@click.group()
def main() -> None:
    """aiko_services_tpu: TPU-native distributed ML pipeline framework."""


@main.command()
@click.option("--name", default="registrar")
@click.option("--transport", default=None,
              help="loopback | mqtt | null (default: auto from env)")
def registrar(name: str, transport: str | None) -> None:
    """Run a service-discovery registrar."""
    from .runtime import Process, Registrar
    process = Process(transport_kind=transport)
    Registrar(process, name=name)
    process.run()


@main.command()
@click.argument("definition", type=click.Path(exists=True))
@click.option("--name", default=None)
@click.option("--transport", default=None)
@click.option("--stream-id", default=None,
              help="Create this stream immediately")
@click.option("--stream-parameters", default="{}",
              help="JSON stream parameters")
@click.option("--frame-data", default=None,
              help="JSON frame data posted to the created stream")
@click.option("--grace-time", default=60.0)
def pipeline(definition: str, name: str | None, transport: str | None,
             stream_id: str | None, stream_parameters: str,
             frame_data: str | None, grace_time: float) -> None:
    """Create and run a pipeline from a JSON definition (reference
    `aiko_pipeline create`, pipeline.py:1444-1528).

    The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says, else in the checkout's fixed `.jax_cache` (the replica
    factory, serve/autoscale.py, passes its fleet directory to children
    through that variable).  AIKO_WARM_WEIGHTS names a descriptor file
    whose tensors are fetched from a live sibling over the transfer
    plane instead of re-running setup()."""
    import json
    import os

    from .pipeline import create_pipeline
    from .runtime import Process, enable_compile_cache
    enable_compile_cache()
    process = Process(transport_kind=transport)
    pipeline_instance = create_pipeline(process, definition, name=name)
    warm_weights = os.environ.get("AIKO_WARM_WEIGHTS")
    if warm_weights:
        # a failed hand-off (expired transfer keys, drained sibling)
        # downgrades to a COLD start -- setup() runs lazily as usual;
        # dying here would turn a scale-up into a failed spawn
        try:
            with open(warm_weights) as handoff:
                installed = pipeline_instance.import_weights(
                    json.load(handoff))
            click.echo(f"warm start: imported weights for {installed}")
        except Exception as error:
            click.echo(f"warm start failed ({error}); starting cold",
                       err=True)
        finally:
            try:  # one-shot descriptor file from the replica factory
                os.unlink(warm_weights)
            except OSError:
                pass
    if stream_id is not None:
        pipeline_instance.create_stream(
            stream_id, parameters=json.loads(stream_parameters),
            grace_time=grace_time)
        if frame_data is not None:
            pipeline_instance.process_frame(
                {"stream_id": stream_id}, json.loads(frame_data))
    process.run()


@main.command()
@click.option("--name", default="storage")
@click.option("--database", default="storage.db")
@click.option("--transport", default=None)
def storage(name: str, database: str, transport: str | None) -> None:
    """Run a sqlite storage service."""
    from .runtime import Process, Storage
    process = Process(transport_kind=transport)
    Storage(process, name=name, database_path=database)
    process.run()


@main.command()
@click.option("--name", default="recorder")
@click.option("--topic", default=None, help="Log topic pattern")
@click.option("--transport", default=None)
def recorder(name: str, topic: str | None, transport: str | None) -> None:
    """Run a log-aggregation recorder service."""
    from .runtime import Process, Recorder
    process = Process(transport_kind=transport)
    Recorder(process, name=name, log_topic_pattern=topic)
    process.run()


@main.command()
@click.option("--transport", default=None)
@click.option("--snapshot", is_flag=True,
              help="Print one services-table snapshot and exit")
@click.option("--wait", default=3.0,
              help="Seconds to wait for discovery in snapshot mode")
def dashboard(transport: str | None, snapshot: bool, wait: float) -> None:
    """Service dashboard: curses TUI, or --snapshot for plain text."""
    from .dashboard import run_dashboard
    run_dashboard(transport_kind=transport, snapshot=snapshot, wait=wait)


@main.command()
@click.argument("sources", nargs=-1, type=click.Path())
@click.option("--strict", is_flag=True,
              help="Fail on warnings too (errors always fail)")
@click.option("--format", "fmt",
              type=click.Choice(["text", "json"]), default="text")
@click.option("--output", default=None, type=click.Path(),
              help="Also write the report to this file")
@click.option("--passes", "passes_option", default=None,
              help="Comma-separated pass list "
                   "(graph,policy,actor,eval); default: all")
@click.option("--bench", "bench_configs", is_flag=True,
              help="Also lint every pipeline definition bench.py "
                   "constructs")
@click.option("--golden", default=None,
              type=click.Path(exists=True, file_okay=False),
              help="Verify a corpus of deliberately-broken definitions:"
                   " each <code>_*.json (or <code>_*.py for the AIKO6xx"
                   " concurrency pass) must produce that rule code")
@click.option("--code", "code_mode", is_flag=True,
              help="Concurrency lint (AIKO6xx) over Python SOURCE "
                   "files/trees instead of pipeline definitions")
@click.option("--baseline", default=None, type=click.Path(),
              help="(--code) accepted-findings baseline JSON: matches "
                   "are filtered, stale entries surface as AIKO600")
@click.option("--update-baseline", "update_baseline", is_flag=True,
              help="(--code) rewrite --baseline from the current "
                   "findings and exit 0")
def lint(sources, strict, fmt, output, passes_option, bench_configs,
         golden, code_mode, baseline, update_baseline) -> None:
    """Statically verify pipeline definitions (analyze/ subsystem).

    SOURCES are definition JSON files or directories (searched
    recursively for *.json).  Four passes: graph/port dataflow
    (AIKO1xx), tensor-spec shape/dtype flow (AIKO2xx, including a
    jax.eval_shape dry-run of element device programs), element/actor
    safety (AIKO3xx), and policy grammars (AIKO4xx).  With --code,
    SOURCES are Python files/trees and the AIKO6xx static concurrency
    pass runs instead (thread-role inference over the actor fleet;
    see README "Concurrency model").  Exit status: 0 clean, 1 findings
    (with --strict, warnings count), 2 usage error.
    """
    import sys
    from pathlib import Path

    from .analyze import ALL_PASSES, AnalysisReport, analyze_definition

    if code_mode:
        sys.exit(_lint_code(sources, strict, fmt, output, baseline,
                            update_baseline))
    if baseline or update_baseline:
        click.echo("--baseline/--update-baseline need --code", err=True)
        sys.exit(2)

    passes = (tuple(part.strip() for part in passes_option.split(",")
                    if part.strip())
              if passes_option else ALL_PASSES)
    unknown = [name for name in passes if name not in ALL_PASSES]
    if unknown:
        click.echo(f"unknown passes: {unknown} (valid: {ALL_PASSES})",
                   err=True)
        sys.exit(2)

    if golden is not None:
        sys.exit(_lint_golden(Path(golden), passes))

    targets: list = []
    for source in sources:
        path = Path(source)
        if path.is_dir():
            targets.extend(sorted(path.rglob("*.json")))
        else:
            targets.append(path)
    if bench_configs:
        import runpy
        bench_path = Path(__file__).resolve().parent.parent / "bench.py"
        if not bench_path.is_file():
            click.echo(f"--bench needs a source checkout: {bench_path} "
                       f"not found", err=True)
            sys.exit(2)
        bench_module = runpy.run_path(str(bench_path))
        for name, definition in sorted(
                bench_module["collect_definitions"]().items()):
            targets.append((f"bench.py::{name}", definition))
    if not targets:
        click.echo("nothing to lint (give files, directories, or "
                   "--bench)", err=True)
        sys.exit(2)

    report = AnalysisReport()
    for target in targets:
        if isinstance(target, tuple):
            label, source = target
        else:
            label, source = str(target), target
        report.extend(analyze_definition(source, passes=passes,
                                         source_path=label))
    rendered = (report.to_json() if fmt == "json"
                else report.render())
    click.echo(rendered)
    if output:
        Path(output).write_text(
            rendered if rendered.endswith("\n") else rendered + "\n")
    sys.exit(1 if report.failures(strict=strict) else 0)


def _lint_code(sources, strict, fmt, output, baseline,
               update_baseline) -> int:
    """`aiko lint --code`: the AIKO6xx static concurrency pass over
    Python source trees, optionally diffed against a committed
    baseline of accepted findings.  Returns the exit status."""
    import sys
    from pathlib import Path

    from .analyze import (
        apply_baseline, load_baseline, run_code_pass, write_baseline)

    if not sources:
        click.echo("nothing to lint (give Python files or directories)",
                   err=True)
        return 2
    missing = [source for source in sources
               if not Path(source).exists()]
    if missing:
        click.echo(f"no such path(s): {missing}", err=True)
        return 2
    report = run_code_pass([Path(source) for source in sources])
    if update_baseline:
        if not baseline:
            click.echo("--update-baseline needs --baseline PATH",
                       err=True)
            return 2
        count = write_baseline(baseline, report)
        click.echo(f"baseline written: {count} accepted finding(s) -> "
                   f"{baseline}")
        return 0
    if baseline:
        try:
            entries = load_baseline(baseline)
        except (OSError, ValueError) as error:
            click.echo(f"cannot read baseline: {error}", err=True)
            return 2
        filtered = apply_baseline(report, entries)
        click.echo(f"baseline: {filtered} accepted finding(s) "
                   f"filtered", err=True)
    rendered = (report.to_json() if fmt == "json" else report.render())
    click.echo(rendered)
    if output:
        Path(output).write_text(
            rendered if rendered.endswith("\n") else rendered + "\n")
    return 1 if report.failures(strict=strict) else 0


def _lint_golden(corpus: "Path", passes) -> int:
    """Golden-corpus mode: every `<code>_*.json` in the corpus must
    yield a finding with that code -- the proof each lint rule still
    fires.  `<code>_*.py` fixtures run through the AIKO6xx concurrency
    pass the same way.  Returns the exit status."""
    from .analyze import RULES, analyze_definition, run_code_pass

    failures = 0
    checked = 0
    for path in sorted(corpus.glob("*.json")) + sorted(
            corpus.glob("*.py")):
        expected = path.stem.split("_", 1)[0].upper()
        if expected not in RULES:
            click.echo(f"SKIP {path.name}: no rule code prefix")
            continue
        checked += 1
        if path.suffix == ".py":
            report = run_code_pass([path], root=corpus)
        else:
            report = analyze_definition(path, passes=passes,
                                        source_path=str(path))
        codes = {diagnostic.code for diagnostic in report.findings}
        if expected in codes:
            click.echo(f"ok   {path.name}: {expected} fired")
        else:
            failures += 1
            click.echo(f"FAIL {path.name}: expected {expected}, got "
                       f"{sorted(codes) or 'no findings'}")
    click.echo(f"{checked} golden definition(s), {failures} failure(s)")
    return 1 if failures or not checked else 0


@main.group()
def deadletter() -> None:
    """Inspect and drain dead-lettered frames after a recovered
    outage: `ls` lists the Recorder's dead-letter ring, `replay`
    re-submits a selected frame through the serving gateway (frames
    small enough to embed their encoded inputs replay exactly; larger
    ones are descriptor-only evidence)."""


def fetch_dead_letters(process, wait: float = 3.0) -> list:
    """Drain the first discovered Recorder's dead-letter ring: decoded
    {"index", "topic", "meta", "descriptor"} records, oldest first.
    Shared by `aiko deadletter ls|replay` and tests."""
    import json
    import threading

    from .runtime import ServiceFilter
    from .runtime.recorder import SERVICE_PROTOCOL_RECORDER
    from .runtime.storage import do_request

    done = threading.Event()
    collected: list = []

    def on_items(items):
        collected.extend(items)
        done.set()

    do_request(process, ServiceFilter(protocol=SERVICE_PROTOCOL_RECORDER),
               lambda proxy, response_topic:
               proxy.deadletters(response_topic),
               on_items)
    done.wait(wait)
    records = []
    for item in collected:
        try:
            records.append(json.loads(item))
        except (TypeError, ValueError):
            continue
    return records


def replay_dead_letter(process, record: dict, gateway_topic: str,
                       create: bool = True, grace_time: float = 60.0,
                       topic_response: str = "") -> bool:
    """Re-submit one dead-lettered frame through a gateway: optionally
    (re)create the stream (a duplicate create gets a harmless typed
    reject), then publish the EXACT embedded frame data under its
    original stream/frame identity -- the gateway's exactly-once dedupe
    makes replay idempotent.  `topic_response` routes the outcome back
    to the caller.  Returns False when the record carries no embedded
    data (it exceeded AIKO_DEAD_LETTER_DATA_MAX)."""
    import json

    from .utils import generate

    meta = record.get("meta") or {}
    data = meta.get("data")
    if not data:
        return False
    stream_id = str(meta.get("stream_id", ""))
    frame_id = meta.get("frame_id", 0)
    if create:
        process.publish(
            f"{gateway_topic}/in",
            generate("create_stream", [
                stream_id, json.dumps({}).encode("ascii"), grace_time,
                topic_response]))
    process.publish(
        f"{gateway_topic}/in",
        generate("process_frame", [
            {"stream_id": stream_id, "frame_id": frame_id},
            str(data).encode("ascii")]))
    return True


def _discover_gateway_topic(process, wait: float) -> str | None:
    import threading

    from .runtime import ServiceFilter
    from .runtime.storage import do_command
    from .serve import SERVICE_PROTOCOL_GATEWAY

    found = threading.Event()
    topics: list = []

    def on_proxy(proxy):
        # RemoteProxy exposes its /in topic; the service root is its
        # parent (any non-underscore attribute would proxy a call)
        topics.append(proxy._topic_in.rsplit("/in", 1)[0])
        found.set()

    do_command(process, ServiceFilter(protocol=SERVICE_PROTOCOL_GATEWAY),
               on_proxy)
    found.wait(wait)
    return topics[0] if topics else None


@deadletter.command("ls")
@click.option("--transport", default=None)
@click.option("--wait", default=3.0, help="Discovery/response wait (s)")
def deadletter_ls(transport: str | None, wait: float) -> None:
    """List the fleet's dead-lettered frames (newest last)."""
    from .runtime import Process
    process = Process(transport_kind=transport)
    process.run(in_thread=True)
    try:
        records = fetch_dead_letters(process, wait=wait)
        if not records:
            click.echo("no dead letters (or no recorder discovered)")
            return
        for record in records:
            meta = record.get("meta") or {}
            click.echo(
                f"[{record.get('index')}] {meta.get('stream_id')}"
                f"/{meta.get('frame_id')} node={meta.get('node')} "
                f"reason={meta.get('reason')} "
                f"data={'yes' if meta.get('data') else 'no'} "
                f"diag={str(meta.get('diagnostic', ''))[:60]}")
    finally:
        process.terminate()


@deadletter.command("replay")
@click.argument("index", type=int)
@click.option("--gateway", default=None,
              help="Gateway topic path (default: discover one)")
@click.option("--transport", default=None)
@click.option("--wait", default=3.0)
@click.option("--create/--no-create", "create_stream", default=True,
              help="Re-create the stream first (idempotent)")
def deadletter_replay(index: int, gateway: str | None,
                      transport: str | None, wait: float,
                      create_stream: bool) -> None:
    """Re-submit dead letter INDEX through the gateway."""
    from .runtime import Process
    process = Process(transport_kind=transport)
    process.run(in_thread=True)
    try:
        records = {record.get("index"): record
                   for record in fetch_dead_letters(process, wait=wait)}
        record = records.get(index)
        if record is None:
            raise click.ClickException(
                f"no dead letter at index {index} "
                f"(have {sorted(records)})")
        topic = gateway or _discover_gateway_topic(process, wait)
        if not topic:
            raise click.ClickException(
                "no gateway given and none discovered")
        import threading

        from .utils import parse
        outcome = {}
        done = threading.Event()
        response_topic = (f"{process.topic_path_process}/0/"
                          f"deadletter_replay")

        def on_response(_topic, payload):
            try:
                command, parameters = parse(payload)
            except ValueError:
                return
            if command == "process_frame_response" and parameters:
                reply = parameters[0] if isinstance(parameters[0],
                                                    dict) else {}
                outcome["status"] = reply.get("event") or "ok"
                done.set()
            elif command == "overloaded":
                outcome["status"] = "overloaded"
                done.set()

        process.add_message_handler(on_response, response_topic)
        if not replay_dead_letter(process, record, topic,
                                  create=create_stream,
                                  topic_response=response_topic):
            raise click.ClickException(
                "record has no embedded frame data (frame exceeded "
                "AIKO_DEAD_LETTER_DATA_MAX when it was dead-lettered)")
        done.wait(wait)
        click.echo(f"replayed {record['meta'].get('stream_id')}"
                   f"/{record['meta'].get('frame_id')} via {topic}: "
                   f"{outcome.get('status', 'no response within wait')}")
    finally:
        process.terminate()


@main.command()
@click.argument("trace", type=click.Path(exists=True), required=False)
@click.option("--live", default=None, metavar="TOPIC",
              help="Tune from a LIVE wire harvest instead of a trace "
                   "artifact: publish_trace the service at TOPIC "
                   "(a gateway or pipeline topic path), or pass "
                   "'discover' to harvest every discovered "
                   "gateway/pipeline -- the same harvest+merge path "
                   "the gateway autopilot runs each tick")
@click.option("--transport", default=None,
              help="Transport for --live (default: AIKO_TRANSPORT)")
@click.option("--wait", default=3.0,
              help="Discovery/response wait for --live (s)")
@click.option("--slo", default="throughput",
              help="SLO directive: 'throughput', 'latency', or a "
                   "spec like 'slo=throughput;p99_ms=250' "
                   "(AIKO501 grammar)")
@click.option("--json", "as_json", is_flag=True,
              help="Machine-readable report (byte-deterministic: the "
                   "same trace + spec always renders identically)")
@click.option("--output", default=None, type=click.Path(),
              help="Also write the report to this file")
@click.option("--definition", "definition_path", default=None,
              type=click.Path(exists=True),
              help="Side-channel definition for metadata-absent "
                   "traces (self-describing traces embed their own)")
@click.option("--run", "run_name", default=None,
              help="Pick one run out of a combined multi-pipeline "
                   "trace artifact")
@click.option("--apply", "apply_path", default=None,
              type=click.Path(),
              help="Write the tuned definition document here (the "
                   "recommendations applied, then re-linted; lint "
                   "errors fail the command)")
@click.option("--what-if", "what_if", default=None,
              help="Re-score the trace under explicit settings "
                   "instead of recommending: "
                   "'asr.micro_batch=4;frame_window=8;replicas=2'")
@click.option("--no-flops", "no_flops", is_flag=True,
              help="Skip the static FLOP/byte estimation (no element "
                   "instantiation -- faster; achieved-utilization "
                   "evidence is omitted)")
def tune(trace, live, transport, wait, slo, as_json, output,
         definition_path, run_name, apply_path, what_if,
         no_flops) -> None:
    """Profile-guided pipeline optimizer: classify each element's
    dominant floor (dispatch / compute / queue / compile-bound) from a
    recorded trace joined against the static graph, recommend concrete
    settings for the stated SLO, and what-if replay them -- no
    hardware needed (tune/ subsystem, README "Performance tuning").

    TRACE is a Perfetto artifact from `bench.py --trace` or
    PipelineTelemetry.export_trace; `--live TOPIC` harvests one over
    the wire instead.  Exit status: 0 report produced, 1 --apply
    produced a definition that fails lint, 2 the trace cannot be
    joined (no metadata and no --definition) or not harvested.
    """
    import sys
    from pathlib import Path

    from .analyze.grammar import GrammarError
    from .tune import (
        SloSpec, TraceLoadError, render_report, report_json, run_tune)

    if (trace is None) == (live is None):
        click.echo("give exactly one trace source: a TRACE artifact "
                   "path or --live TOPIC", err=True)
        sys.exit(2)
    if live is not None and what_if is not None:
        # what-if replays a SPECIFIC recorded trace under explicit
        # settings; a live harvest is point-in-time and unrepeatable,
        # so the comparison would be against a moving target
        click.echo("--what-if needs a trace artifact (record one with "
                   "bench.py --trace), not --live", err=True)
        sys.exit(2)
    if what_if is not None and apply_path is not None:
        # --what-if scores EXPLICIT settings (no recommender), so
        # there is nothing to apply -- silently ignoring --apply
        # would hand a success exit code and no output file
        click.echo("--what-if and --apply are mutually exclusive: "
                   "what-if scores explicit settings without "
                   "producing recommendations to apply", err=True)
        sys.exit(2)
    try:
        slo_spec = SloSpec.parse(slo)
    except GrammarError as error:
        click.echo(f"bad --slo spec: {error}", err=True)
        sys.exit(2)
    static_costs = {} if no_flops else None
    loaded = None
    try:
        if live is not None:
            # the gateway autopilot's exact harvest+merge+tune path
            # (serve/autopilot.py), run once from the shell: wire-
            # harvest, merge, tune -- no artifact file ever written
            from .runtime import Process
            from .serve.autopilot import harvest_documents, \
                tune_documents
            process = Process(transport_kind=transport)
            process.run(in_thread=True)
            try:
                targets = None if live == "discover" else [live]
                named = harvest_documents(process, wait=wait,
                                          targets=targets)
            finally:
                process.terminate()
            if not named:
                click.echo(
                    f"no traces harvested: nothing answered "
                    f"publish_trace within {wait:g}s "
                    f"({'discovery' if live == 'discover' else live})",
                    err=True)
                sys.exit(2)
            if apply_path is not None:
                # one parse serves both the report and the apply
                from .observe import merge_trace_documents
                from .tune import load_trace
                loaded = load_trace(
                    "live", definition=definition_path, run=run_name,
                    document=merge_trace_documents(list(named)))
                report = run_tune("live", slo_spec=slo_spec,
                                  loaded=loaded,
                                  static_costs=static_costs)
            else:
                report = tune_documents(
                    named, slo_spec=slo_spec,
                    definition=definition_path, run=run_name,
                    static_costs=static_costs)
        elif what_if is not None:
            report = _tune_what_if(trace, slo_spec, definition_path,
                                   run_name, what_if,
                                   static_costs=static_costs)
        else:
            if apply_path is not None:
                # one parse serves both the report and the apply
                from .tune import load_trace
                loaded = load_trace(trace, definition=definition_path,
                                    run=run_name)
            report = run_tune(trace, slo_spec=slo_spec,
                              definition=definition_path,
                              run=run_name,
                              static_costs=static_costs,
                              loaded=loaded)
    except TraceLoadError as error:
        click.echo(str(error), err=True)
        sys.exit(2)
    if not report.get("pipeline") and what_if is None:
        # nothing joined: the trace carries spans but no definition
        # (metadata absent and no side channel, or an ambiguous
        # combined artifact) -- fail loudly instead of printing floors
        # that cannot be attributed to typed nodes
        for diagnostic in report.get("diagnostics", []):
            click.echo(f"{diagnostic['code']}: "
                       f"{diagnostic['message']}", err=True)
        click.echo("trace not joined to a definition: give "
                   "--definition for a metadata-absent trace (or "
                   "--run for a combined one)", err=True)
        sys.exit(2)
    rendered = (report_json(report) if as_json
                else render_report(report))
    click.echo(rendered)
    if output:
        Path(output).write_text(
            rendered if rendered.endswith("\n") else rendered + "\n")
    if apply_path is not None and what_if is None:
        sys.exit(_tune_apply(loaded, report, apply_path))


_WHAT_IF_ELEMENT_KNOBS = ("micro_batch", "decode_slots",
                          "kv_block_size")
_WHAT_IF_PIPELINE_KNOBS = ("frame_window", "replicas")


def _parse_what_if(spec: str, element_names) -> dict:
    """'element.knob=value;knob=value' -> replay overrides.  Unknown
    elements/knobs are usage errors: a typo'd override would
    otherwise be silently ignored and the what-if replay would print
    baseline numbers as the proposed score."""
    overrides: dict = {"elements": {}}
    for part in str(spec).split(";"):
        part = part.strip()
        if not part:
            continue
        key, _, value = part.partition("=")
        try:
            number = int(value)
        except ValueError:
            raise click.ClickException(
                f"--what-if value {value!r} is not an integer "
                f"(in {part!r})")
        if "." in key:
            element, knob = (token.strip()
                             for token in key.split(".", 1))
            if element not in element_names:
                raise click.ClickException(
                    f"--what-if names unknown element {element!r} "
                    f"(trace has {sorted(element_names)})")
            if knob not in _WHAT_IF_ELEMENT_KNOBS:
                raise click.ClickException(
                    f"--what-if element knob {knob!r} is not one of "
                    f"{_WHAT_IF_ELEMENT_KNOBS}")
            overrides["elements"].setdefault(element, {})[
                knob] = number
        else:
            knob = key.strip()
            if knob not in _WHAT_IF_PIPELINE_KNOBS:
                raise click.ClickException(
                    f"--what-if knob {knob!r} is not one of "
                    f"{_WHAT_IF_PIPELINE_KNOBS} (element knobs are "
                    f"'element.knob=value')")
            overrides[knob] = number
    return overrides


def _tune_what_if(trace, slo_spec, definition_path, run_name, what_if,
                  static_costs=None) -> dict:
    """Score explicit settings against the recorded cost model -- no
    recommender in the loop, so CI can pin pure replay determinism."""
    from .tune import (
        CostModel, build_report, classify_elements,
        element_settings_of, load_trace, predict)
    loaded = load_trace(trace, definition=definition_path,
                        run=run_name)
    if static_costs is None:
        static_costs = {}
        if loaded.definition is not None:
            from .analyze.shape_eval import element_cost_estimates
            try:
                static_costs = element_cost_estimates(
                    loaded.definition)
            except Exception:
                static_costs = {}
    model = CostModel.from_trace(
        loaded, static_costs=static_costs,
        dispatch_floor_s=slo_spec.dispatch_floor_s,
        peak_flops=slo_spec.peak_flops)
    classify_elements(model)
    settings = element_settings_of(loaded.definition_document)
    baseline = predict(model, settings)
    overrides = _parse_what_if(what_if, set(loaded.elements))
    proposed = predict(model, settings, overrides)
    return build_report(loaded, model, slo_spec, [], baseline,
                        proposed)


def _tune_apply(loaded, report, apply_path) -> int:
    """Write the tuned definition (from the ALREADY-loaded trace) and
    re-lint it.  Returns the exit status (0 clean, 1 the applied
    definition fails lint)."""
    import json as json_module
    from pathlib import Path

    from .analyze import analyze_definition
    from .tune import Recommendation, apply_recommendations

    if loaded is None or loaded.definition_document is None:
        click.echo("--apply needs a definition (embedded metadata or "
                   "--definition)", err=True)
        return 2
    recommendations = [
        Recommendation(**{key: record[key] for key in
                          ("target", "knob", "current", "proposed",
                           "reason", "floor", "evidence")})
        for record in report.get("recommendations", [])]
    document, diagnostics = apply_recommendations(
        loaded.definition_document, recommendations)
    for diagnostic in diagnostics:
        click.echo(diagnostic.render(), err=True)
    lint_report = analyze_definition(document,
                                     passes=("graph", "policy"))
    Path(apply_path).write_text(
        json_module.dumps(document, indent=2) + "\n")
    failures = lint_report.failures()
    if failures:
        click.echo(f"applied definition FAILS lint "
                   f"({len(failures)} error(s)):", err=True)
        for diagnostic in failures:
            click.echo(f"  {diagnostic.render()}", err=True)
        return 1
    click.echo(f"applied {len(recommendations)} recommendation(s) -> "
               f"{apply_path} (lint clean)")
    return 0


@main.group("trace")
def trace_group() -> None:
    """Fleet-scope distributed tracing: harvest per-process Perfetto
    artifacts from a live fleet (`collect`) and merge many artifacts
    into ONE clock-aligned timeline (`merge`) -- the input `aiko tune`
    reads for cross-process (admission-bound) floor classification."""


@trace_group.command("merge")
@click.argument("output", type=click.Path())
@click.argument("inputs", type=click.Path(exists=True), nargs=-1,
                required=True)
def trace_merge(output: str, inputs) -> None:
    """Merge trace artifacts into OUTPUT.  Inputs are sorted (basename,
    path) before merging, so the same file set always produces
    byte-identical output -- CI diffs two merges to prove it."""
    import sys

    from .observe import merge_trace_files, trace_summary
    try:
        merged = merge_trace_files(list(inputs), output=output)
    except (OSError, ValueError) as error:
        click.echo(f"merge failed: {error}", err=True)
        sys.exit(2)
    summary = trace_summary(merged)
    click.echo(
        f"merged {len(inputs)} artifact(s) -> {output}: "
        f"{len(merged['traceEvents'])} events, "
        f"{summary['traces']} trace(s), "
        f"{summary['multi_process_traces']} crossing processes "
        f"(max {summary['max_processes_per_trace']} processes/trace), "
        f"{summary['linked_spans']} parent-linked span(s)")
    if summary["dangling_parents"]:
        click.echo(
            f"warning: {len(summary['dangling_parents'])} span(s) name "
            f"a parent outside the merged set (partial harvest?)",
            err=True)


@trace_group.command("collect")
@click.option("--output", "output_dir", type=click.Path(),
              required=True,
              help="Directory for the per-process artifacts")
@click.option("--merge", "merge_path", type=click.Path(), default=None,
              help="Also write the merged artifact here")
@click.option("--transport", default=None)
@click.option("--wait", default=3.0,
              help="Discovery/response wait (s)")
def trace_collect(output_dir: str, merge_path: str | None,
                  transport: str | None, wait: float) -> None:
    """Harvest every live pipeline/gateway's trace document over the
    control plane (each replies to `(publish_trace ...)` with its
    self-describing artifact) into per-process files, optionally
    merged."""
    import json as json_module
    import sys
    from pathlib import Path

    from .observe import collect_traces, merge_trace_documents
    from .runtime import Process
    process = Process(transport_kind=transport)
    process.run(in_thread=True)
    try:
        collected = collect_traces(process, wait=wait)
    finally:
        process.terminate()
    if not collected:
        click.echo("no traces collected (no live pipelines/gateways "
                   "discovered, or telemetry disabled)", err=True)
        sys.exit(2)
    directory = Path(output_dir)
    directory.mkdir(parents=True, exist_ok=True)
    from .observe.collector import unique_source_name
    named = []
    seen: dict = {}
    for source in sorted(collected):
        safe = unique_source_name(
            seen, source.replace("/", "_").strip("_"))
        path = directory / f"{safe}.json"
        path.write_text(json_module.dumps(collected[source],
                                          sort_keys=True))
        named.append((safe, collected[source]))
        click.echo(f"collected {source} -> {path}")
    if merge_path:
        merged = merge_trace_documents(named)
        Path(merge_path).write_text(json_module.dumps(
            merged, sort_keys=True, separators=(",", ":")))
        click.echo(f"merged {len(named)} artifact(s) -> {merge_path}")


@main.command()
def bench() -> None:
    """Run the standard benchmark (one JSON line)."""
    import runpy
    from pathlib import Path
    bench_path = Path(__file__).resolve().parent.parent / "bench.py"
    runpy.run_path(str(bench_path), run_name="__main__")


@main.group()
def system() -> None:
    """One-command bootstrap: start/stop a whole local deployment
    (registrar + dashboard + a named pipeline) as detached OS
    processes tracked in a state file."""


DEFAULT_STATE_FILE = ".aiko_system.json"


def _system_state(state_file: str) -> dict:
    import json
    from pathlib import Path
    path = Path(state_file)
    if not path.is_file():
        return {}
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def _pid_alive(pid: int) -> bool:
    import os
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def _pid_is_ours(pid: int) -> bool:
    """Guard against pid reuse: a state file that outlives its children
    (reboot, crash) must not let `aiko system stop` signal whatever
    unrelated process now owns the pid.  Where /proc is unavailable the
    check passes — liveness alone decides, as before."""
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as handle:
            return b"aiko_services_tpu" in handle.read()
    except OSError:
        return True


@system.command("start")
@click.argument("definition", type=click.Path(exists=True))
@click.option("--name", default=None, help="Pipeline service name")
@click.option("--transport", default=None,
              help="loopback | mqtt | null (default: auto from env)")
@click.option("--dashboard/--no-dashboard", "with_dashboard",
              default=False,
              help="Also spawn the curses dashboard (opt-in: as a "
                   "background child it shares this shell's terminal, "
                   "so prefer `aiko dashboard` in its own terminal)")
@click.option("--state-file", default=DEFAULT_STATE_FILE,
              help="Where the spawned pids are recorded for `aiko "
                   "system stop`")
def system_start(definition: str, name: str | None,
                 transport: str | None, with_dashboard: bool,
                 state_file: str) -> None:
    """Spawn registrar (+ optional dashboard) + the DEFINITION
    pipeline.

    Children are detached `python -m aiko_services_tpu <command>`
    processes (ProcessManager with start_new_session, so they survive
    this shell closing); the command returns immediately and `aiko
    system stop` terminates everything it started."""
    import json
    import subprocess
    import sys
    import time
    from pathlib import Path

    from .runtime import ProcessManager

    state = _system_state(state_file)
    alive = {service: pid for service, pid
             in (state.get("pids") or {}).items()
             if _pid_alive(pid) and _pid_is_ours(pid)}
    if alive:
        click.echo(f"already running ({state_file}): {alive} -- "
                   f"`aiko system stop` first", err=True)
        sys.exit(1)

    transport_args = (["--transport", transport] if transport else [])
    manager = ProcessManager()
    services = {}
    logs = {}

    def spawn(service_id, *arguments, inherit_stdio=False):
        # own log file per child: an inherited stdout/stderr would pin
        # any pipe on this shell open and die with its terminal.  The
        # curses dashboard is the exception -- it NEEDS the tty.
        if inherit_stdio:
            child = manager.spawn(
                service_id, sys.executable,
                ["-m", "aiko_services_tpu", *arguments],
                use_interpreter=False, start_new_session=True)
        else:
            log_path = Path(state_file).with_suffix(
                "." + service_id.replace(":", "_") + ".log")
            with open(log_path, "ab") as log:
                child = manager.spawn(
                    service_id, sys.executable,
                    ["-m", "aiko_services_tpu", *arguments],
                    use_interpreter=False, start_new_session=True,
                    stdout=log, stderr=subprocess.STDOUT)
            logs[service_id] = str(log_path)
        services[service_id] = child.pid
        return child

    spawn("registrar", "registrar", *transport_args)
    pipeline_args = ["pipeline", str(Path(definition).resolve()),
                     *transport_args]
    if name:
        pipeline_args += ["--name", name]
    spawn(f"pipeline:{name or Path(definition).stem}", *pipeline_args)
    if with_dashboard:
        if not sys.stdout.isatty():
            click.echo("--dashboard needs a terminal (curses); "
                       "skipping -- run `aiko dashboard` instead",
                       err=True)
        else:
            spawn("dashboard", "dashboard", *transport_args,
                  inherit_stdio=True)
    Path(state_file).write_text(json.dumps({
        "pids": services,
        "logs": logs,
        "definition": str(Path(definition).resolve()),
        "transport": transport,
        "started": time.time(),
    }, indent=2) + "\n")
    for service_id, pid in services.items():
        log_note = (f" (log {logs[service_id]})"
                    if service_id in logs else "")
        click.echo(f"started {service_id}: pid {pid}{log_note}")
    click.echo(f"state: {state_file} -- stop with `aiko system stop"
               + (f" --state-file {state_file}`"
                  if state_file != DEFAULT_STATE_FILE else "`"))


@system.command("stop")
@click.option("--state-file", default=DEFAULT_STATE_FILE)
@click.option("--timeout", default=10.0,
              help="Seconds to wait after SIGTERM before SIGKILL")
def system_stop(state_file: str, timeout: float) -> None:
    """Terminate every process `aiko system start` recorded: SIGTERM,
    a grace wait, then SIGKILL for stragglers."""
    import os
    import signal
    import sys
    import time
    from pathlib import Path

    state = _system_state(state_file)
    pids = state.get("pids") or {}
    if not pids:
        click.echo(f"nothing recorded in {state_file}", err=True)
        sys.exit(1)
    recycled = set()
    for service_id, pid in pids.items():
        if not _pid_alive(pid):
            click.echo(f"{service_id}: pid {pid} already gone")
        elif not _pid_is_ours(pid):
            recycled.add(service_id)
            click.echo(f"{service_id}: pid {pid} is no longer an "
                       f"aiko_services_tpu process (recycled after a "
                       f"reboot?) -- leaving it alone", err=True)
        else:
            try:
                os.kill(pid, signal.SIGTERM)
                click.echo(f"stopping {service_id}: pid {pid}")
            except OSError as error:
                click.echo(f"stop {service_id} pid {pid}: {error}",
                           err=True)
    deadline = time.monotonic() + timeout
    remaining = {service: pid for service, pid in pids.items()
                 if service not in recycled}
    while remaining and time.monotonic() < deadline:
        remaining = {service: pid for service, pid in remaining.items()
                     if _pid_alive(pid)}
        time.sleep(0.05)
    for service_id, pid in remaining.items():
        try:
            os.kill(pid, signal.SIGKILL)
            click.echo(f"killed {service_id}: pid {pid} (no SIGTERM "
                       f"exit within {timeout}s)")
        except OSError:
            pass
    Path(state_file).unlink(missing_ok=True)
    click.echo("stopped")


def _print_replica_pools(transport: str | None, wait: float) -> int:
    """Discover serving gateways through the registrar and print each
    one's replica pool (replica topic, state, load gauges, warm/cold)
    from its EC share -- rendered by the SAME plugin the dashboard
    uses, so the two views cannot drift.  Returns the number of
    gateways found."""
    import time
    from types import SimpleNamespace

    from .dashboard import _gateway_plugin
    from .runtime import Process
    from .runtime.service import ServiceFilter
    from .runtime.share import ECConsumer, services_cache_create_singleton

    process = Process(transport_kind=transport)
    gateways: dict = {}

    def handler(command, fields):
        if command == "add":
            gateways[fields.topic_path] = fields

    cache = services_cache_create_singleton(process)
    # protocols are full URLs ("github.com/.../protocol/gateway:0"):
    # the pattern must match the whole string, not just the tail word
    cache.add_handler(handler, ServiceFilter(protocol="*/gateway:*"))
    process.run(in_thread=True)
    try:
        deadline = time.monotonic() + wait
        while time.monotonic() < deadline and not gateways:
            time.sleep(0.05)
        if not gateways:
            click.echo("pool: no gateway services discovered "
                       f"(waited {wait}s)")
            return 0
        # snapshot: the discovery handler keeps appending from the
        # message-pump thread, and a gateway arriving after this point
        # simply waits for the next invocation
        found = sorted(gateways.items())
        shares = {topic_path: {} for topic_path, _ in found}
        consumers = [ECConsumer(process, shares[topic_path], topic_path)
                     for topic_path, _ in found]
        # give the share mirrors until the deadline to fill in; the
        # pool detail rides the periodic telemetry summary
        while (time.monotonic() < deadline
               and not all(shares.values())):
            time.sleep(0.05)
        for topic_path, fields in found:
            click.echo(f"gateway {fields.name} ({topic_path})")
            model = SimpleNamespace(selected_share=shares[topic_path])
            for line in _gateway_plugin(model):
                click.echo(f"  {line}")
        for consumer in consumers:
            consumer.terminate()
        return len(found)
    finally:
        process.terminate()


@system.command("status")
@click.option("--state-file", default=DEFAULT_STATE_FILE)
@click.option("--pool/--no-pool", "show_pool", default=False,
              help="Also discover serving gateways via the registrar "
                   "and print each replica pool (state, load gauges, "
                   "warm/cold)")
@click.option("--transport", default=None,
              help="Transport for --pool discovery (default: the "
                   "start-time transport from the state file)")
@click.option("--wait", default=3.0,
              help="Seconds to wait for --pool discovery")
def system_status(state_file: str, show_pool: bool,
                  transport: str | None, wait: float) -> None:
    """Liveness of every recorded process; --pool adds the serving
    tier's replica pools."""
    import sys
    state = _system_state(state_file)
    pids = state.get("pids") or {}
    if not pids and not show_pool:
        click.echo(f"nothing recorded in {state_file}")
        sys.exit(1)
    logs = state.get("logs") or {}
    down = 0
    for service_id, pid in pids.items():
        alive = _pid_alive(pid)
        down += 0 if alive else 1
        suffix = f"  {logs[service_id]}" if service_id in logs else ""
        click.echo(f"{service_id:24} pid {pid:<8} "
                   f"{'up' if alive else 'DOWN'}{suffix}")
    if show_pool:
        _print_replica_pools(transport or state.get("transport"), wait)
    sys.exit(1 if down else 0)


@main.command()
@click.option("--port", default=None, type=int,
              help="UDP port to answer on (default 4149)")
@click.option("--mqtt-host", default=None,
              help="Broker host to advertise (default: resolved from "
                   "AIKO_MQTT_HOST/AIKO_MQTT_HOSTS with a TCP probe)")
@click.option("--mqtt-port", default=None, type=int)
def bootstrap(port: int | None, mqtt_host: str | None,
              mqtt_port: int | None) -> None:
    """MCU bootstrap responder: answers UDP boot datagrams with the
    namespace + broker endpoint (reference configuration.py:168-186)."""
    import signal
    import time

    from .utils import BootstrapResponder
    kwargs = {"mqtt_host": mqtt_host, "mqtt_port": mqtt_port}
    if port is not None:
        kwargs["port"] = port
    responder = BootstrapResponder(**kwargs)
    click.echo(f"bootstrap responder on udp/{responder.port} advertising "
               f"{responder.mqtt_host}:{responder.mqtt_port}")
    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(True))
    signal.signal(signal.SIGINT, lambda *_: stop.append(True))
    while not stop:
        time.sleep(0.2)
    responder.close()


if __name__ == "__main__":
    main()
