"""Least work of a gather over a scalar table, as any implementation must
do it: per in-edge of an updated vertex its source index and
``flops_per_edge`` operations; each source read once per sweep; each
updated vertex's row written once.  The configuration's ``work.gather``
states the widths and per-edge costs.  Applies where the table is scalar."""


def work(counts, cfg):
    g = cfg["work"]["gather"]
    if g["source_width"] != 1:
        return None
    edges = counts.updated_edges()
    nbytes = (g["edge_bytes"] * edges + 4 * counts.neighbor_reads()
              + 4 * g["output_width"] * counts.updates())
    return g["flops_per_edge"] * edges, nbytes
