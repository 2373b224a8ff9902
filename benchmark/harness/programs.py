"""Finding a jitted program in a reduced trace by its stable name."""

from __future__ import annotations


def programs_of(trace: dict | None, module: str) -> list:
    """The reduced trace's programs whose module is `module`, in order
    of device time."""
    found = [facts for facts in ((trace or {}).get("programs") or {}
                                 ).values() if facts["module"] == module]
    return sorted(found, key=lambda facts: -facts["seconds"])


def runs_of(trace: dict | None, module: str) -> list:
    """Device seconds of every whole execution of `module`'s programs."""
    return [seconds for facts in programs_of(trace, module)
            for seconds in facts["run_seconds"]]


def stage_program(run, node: str):
    """The fused group program of graph stage `node`.  All of them are
    `jit_fused`; the driver says which rank of device time is which."""
    fused = programs_of(run.trace, "jit_fused")
    order = list(run.stage_order or ())
    if node not in order or order.index(node) >= len(fused):
        return None
    return fused[order.index(node)]
