"""Seconds from the package's import to the first `aiko:setup.weights`
interval opening (gauge `setup.boot_s`): the interpreter, jax and the
package imported, the backend started, processes, pipeline and gateway
built.  None where the program keeps no such record."""
from benchmark.harness import startup


def read(run):
    return startup.gauge_s("boot")
