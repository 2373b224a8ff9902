# Tune directive grammar: the operator-facing SLO/knob spec `aiko
# tune` is pointed at, parsed through the SAME shared directive core
# (analyze/grammar.py) as the fault, admission, autoscale, and journal
# grammars -- so a typo'd SLO is an offline AIKO501 lint finding (a
# definition may pin its intended operating point in a `tune`
# parameter), and the CLI and `aiko lint` can never disagree about
# what a valid spec is.
#
# Grammar (`;`-separated key=value):
#
#   slo=throughput|latency      optimization objective (default
#                               throughput)
#   p99_ms=<float>              explicit p99 frame-latency budget: the
#                               recommender may trade throughput knobs
#                               away until the what-if replay predicts
#                               p99 under budget (tighter budgets can
#                               only LOWER micro_batch -- monotonicity
#                               is tested)
#   dispatch_floor_ms=<float>   per-call dispatch floor used by the
#                               floor classifier (default 1.5 ms;
#                               chip_smoke.py's `link` phase prints
#                               the trivial-call time of today's chip)
#   peak_tflops=<float>         per-chip peak for achieved-utilization
#                               evidence (default: from the trace's
#                               embedded bench config block)
#   max_micro_batch=<int>       recommendation ceiling (default 64)
#   max_replicas=<int>          recommendation ceiling (default 8)
#
# Shorthand: a bare "throughput" / "latency" means "slo=<word>".

from __future__ import annotations

from dataclasses import dataclass

from ..analyze.grammar import DirectiveGrammar, Field

__all__ = ["TUNE_GRAMMAR", "SloSpec", "check_tune_spec"]

DEFAULT_DISPATCH_FLOOR_MS = 1.5
DEFAULT_MAX_MICRO_BATCH = 64
DEFAULT_MAX_REPLICAS = 8

TUNE_GRAMMAR = DirectiveGrammar(
    "tune",
    options={
        "slo": Field("str", choices=("throughput", "latency")),
        "p99_ms": Field("float", minimum=1e-3),
        "dispatch_floor_ms": Field("float", minimum=0.0),
        "peak_tflops": Field("float", minimum=0.0),
        "max_micro_batch": Field("int", minimum=1),
        "max_replicas": Field("int", minimum=1),
    },
)


def _normalize(spec) -> str | dict | None:
    if spec is None:
        return None
    if isinstance(spec, dict):
        return spec
    text = str(spec).strip()
    if text.lower() in ("throughput", "latency"):
        return f"slo={text.lower()}"
    return text


@dataclass
class SloSpec:
    """One parsed tune directive spec."""

    objective: str = "throughput"       # throughput | latency
    p99_budget_s: float | None = None
    dispatch_floor_s: float = DEFAULT_DISPATCH_FLOOR_MS / 1000.0
    peak_flops: float | None = None
    max_micro_batch: int = DEFAULT_MAX_MICRO_BATCH
    max_replicas: int = DEFAULT_MAX_REPLICAS
    spec: str = ""

    @classmethod
    def parse(cls, spec) -> "SloSpec":
        """Parse with full validation (GrammarError on a bad spec)."""
        parsed = TUNE_GRAMMAR.parse(_normalize(spec))
        options = parsed.options
        slo = cls(spec="" if spec is None else str(spec))
        slo.objective = options.get("slo", "throughput")
        if "p99_ms" in options:
            slo.p99_budget_s = options["p99_ms"] / 1000.0
        if "dispatch_floor_ms" in options:
            slo.dispatch_floor_s = options["dispatch_floor_ms"] / 1000.0
        if "peak_tflops" in options:
            slo.peak_flops = options["peak_tflops"] * 1e12
        slo.max_micro_batch = options.get("max_micro_batch",
                                          DEFAULT_MAX_MICRO_BATCH)
        slo.max_replicas = options.get("max_replicas",
                                       DEFAULT_MAX_REPLICAS)
        return slo


def check_tune_spec(spec) -> list:
    """(code, message) problems in a tune directive spec -- the
    `aiko lint` surface (AIKO501; unknown directives are AIKO404),
    validated by the SAME grammar SloSpec.parse uses."""
    return TUNE_GRAMMAR.check(_normalize(spec), value_code="AIKO501")
