"""Parsa core: the paper's primary contribution (Algorithms 1–4)."""
from .bipartite import BipartiteGraph, from_edges, load_npz  # noqa: F401
from .bucket_queue import BucketQueue  # noqa: F401
from .costs import PartitionMetrics, evaluate, improvement, need_matrix, random_parts  # noqa: F401
from .partition_v import partition_v  # noqa: F401
from .subgraphs import divide  # noqa: F401
from .parallel import global_initialization  # noqa: F401
