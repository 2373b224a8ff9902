"""Share of device-busy time spent in the detector stage's fused group
program in the traced window, %."""
from benchmark.harness.programs import stage_program


def read(run):
    program = stage_program(run, "detector")
    if program is None or not run.trace.get("busy_s"):
        return None
    return program["seconds"] / run.trace["busy_s"] * 100
