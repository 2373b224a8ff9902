"""The rehearsals run on the CPU: nothing here describes a TPU topology
or loads libtpu."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
