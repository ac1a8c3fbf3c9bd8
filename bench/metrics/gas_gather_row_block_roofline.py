"""Roofline share of the GAS gather over wide tables (the row-block
kernel, ``kernels/gas/gas.py``), found in the trace by its jitted entry's
name: in a cell whose fused gathers are all wider than one lane, every
call of the entry takes the row-block layout."""
from bench.roofline import share


def read(run):
    return share(run, "gas_gather_combine_pallas", "gas_gather_row_block")
