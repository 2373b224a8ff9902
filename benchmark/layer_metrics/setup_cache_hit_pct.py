"""Share of the program's compile-cache requests that the persistent
cache answered (`setup.cache_hits` over `setup.cache_requests`): near 0
on a cold cache, near 100 on a warm one; read every other start-up
number beside it.  None where the program keeps no such record."""
from benchmark.harness import startup


def read(run):
    return startup.cache_hit_pct()
