from .kernel import TILE, cluster_batch_pallas
from .ops import batch_rows, cluster_batch, from_tiles, to_tiles
from .ref import cluster_batch_ref
