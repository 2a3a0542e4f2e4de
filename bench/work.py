"""Work the algorithms need per edge, counted from the shapes.

These are the least bytes any implementation of a body has to move, as the
paper's algorithms define the body's work.  They do not depend on how the
program implements the body (jnp, Pallas or a fused kernel), so a roofline
share built on them cannot pass 100% unless the time is measured wrong.
Vertex ids, degrees, cluster ids, cluster volumes and partition ids are
4-byte words; the replica state is one bit per (vertex, partition), read as
the 4-byte word that holds it.

Both bodies do a few integer operations per edge and no floating-point
work worth counting against a 197 TFLOP/s peak, so their roofline is the
memory bound.
"""
from __future__ import annotations

WORD = 4

#: Algorithm 1, one edge of the clustering pass: read the edge (2 ids),
#: the two cluster ids, the two degrees and the two cluster volumes.  A
#: move writes more, but not every edge moves, so it is not counted.
CLUSTER_STEP_BYTES = (2 + 2 + 2 + 2) * WORD

#: Algorithm 2, one edge of the scoring pass.  Every edge: read the edge,
#: the two cluster ids and the two partitions of those clusters, which
#: decide whether step 2 already placed it.
SCORE_SKIP_BYTES = (2 + 2 + 2) * WORD
#: An edge that is scored besides: the two degrees, the two cluster
#: volumes, the four replica words (each endpoint on each candidate), the
#: partition it gets, and the two replica words it sets (read and write).
SCORE_EDGE_BYTES = (2 + 2 + 4 + 1 + 2 * 2) * WORD


def cluster_step_bytes(edges: int) -> int:
    return edges * CLUSTER_STEP_BYTES


def score_chunk_bytes(edges: int, scored_share: float) -> float:
    """Bytes of ``edges`` scoring-pass edges of which ``scored_share`` were
    left over by pre-partitioning."""
    return edges * (SCORE_SKIP_BYTES + scored_share * SCORE_EDGE_BYTES)
