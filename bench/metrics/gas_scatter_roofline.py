"""Roofline share of the GAS scatter/reschedule kernel
(``kernels/gas/gas.py``), found in the trace by its jitted entry's name."""
from bench.roofline import share


def read(run):
    return share(run, "gas_scatter_reschedule_pallas", "gas_scatter")
