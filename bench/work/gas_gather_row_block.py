"""Least work of a gather over wide tables, as any implementation must do
it: per in-edge of an updated vertex, ``2·w`` operations (a multiply and
an add per lane) for each leaf of width ``w``, its sender index once and
its weight for each leaf; each neighbor's row read once per sweep for each
leaf; each updated vertex's row written once for each leaf.  The widths
are the configuration's ``work.gather.leaf_widths``, as the program
declares them, not the kernel's padded planes.  Applies where they are
given."""


def work(counts, cfg):
    widths = cfg["work"]["gather"].get("leaf_widths")
    if not widths:
        return None
    edges = counts.updated_edges()
    rows = counts.neighbor_reads() + counts.updates()
    nbytes = 4 * edges * (1 + len(widths)) + 4 * sum(widths) * rows
    return 2 * sum(widths) * edges, nbytes
