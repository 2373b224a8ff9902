"""Mean share of the engine's decode slots in use over the window, from
`engine_stats()` sampled every 50 ms by the harness."""
import statistics


def read(run):
    start, end = run.window
    active = [sample[2] for sample in run.samples or ()
              if start <= sample[0] <= end]
    return statistics.fmean(active) / run.slots * 100 if active else None
