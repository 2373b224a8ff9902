"""Seconds from the package's import to the newest start-up interval
closing (gauge `setup.ready_s`): the program had made and compiled
everything it runs.  `setup_s` less this is the harness's warm-up
executions, its warm-in load and its traffic.  None where the program
keeps no such record."""
from benchmark.harness import startup


def read(run):
    return startup.gauge_s("ready")
