"""Roofline share of the GAS gather over scalar tables (the edge-major
kernel, ``kernels/gas/gas.py``), found in the trace by its jitted entry's
name."""
from bench.roofline import share


def read(run):
    return share(run, "gas_gather_combine_pallas", "gas_gather_edge_major")
