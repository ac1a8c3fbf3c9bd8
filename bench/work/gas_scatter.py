"""Least work of the reschedule scatter: per out-edge of an updated vertex
its receiver index and one add; each updated vertex's contribution read
once; each receiver's priority read and written once per sweep."""


def work(counts, cfg):
    edges = counts.updated_edges()
    nbytes = 4 * edges + 4 * counts.updates() + 8 * counts.neighbor_reads()
    return edges, nbytes
