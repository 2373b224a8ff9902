#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that holds the chip: it builds the cell's system with
weights made from the seed, warms this cell's shapes and no others,
measures for --seconds, checks what the timed path produced against the
plain reference, and prints one JSON object as the last line of stdout
(`correct`, `attempted`, `failed`, `metrics`, `device`, and `breakdown`
with --trace 1).  No TPU is an error, never a fallback.
"""

import time

STARTED_AT = time.perf_counter()

import argparse      # noqa: E402
import faulthandler  # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

# a run that is stuck says where: every thread's stack goes to stderr
# after 330 s (a run has 360 s, its first in a checkout 1200 s), and the
# process gives up with a non-zero code shortly before the longer limit
faulthandler.dump_traceback_later(330, exit=False)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)





def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    import importlib

    import aiko_services_tpu  # noqa: F401  (the system under test)
    from benchmark.harness import cells

    manifest = cells.load_manifest()
    cell = cells.load_cell(args.workload, manifest)
    # a configuration names the kind of system it is; the driver for a
    # kind is benchmark/harness/<system>_driver.py
    driver = importlib.import_module(
        f"benchmark.harness.{cell.config['system']}_driver")
    out_dir = os.path.join(ROOT, ".bench_out")
    os.makedirs(out_dir, exist_ok=True)
    line = driver.run(cell, manifest, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), started_at=STARTED_AT,
                      out_dir=out_dir)
    faulthandler.cancel_dump_traceback_later()
    sys.stdout.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
