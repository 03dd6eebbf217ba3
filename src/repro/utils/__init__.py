from repro.utils.tree import (
    tree_bytes,
    tree_count,
    tree_global_norm,
    tree_zeros_like,
)
from repro.utils.compat import make_mesh
from repro.utils.logging import get_logger

__all__ = [
    "tree_bytes",
    "tree_count",
    "tree_global_norm",
    "tree_zeros_like",
    "make_mesh",
    "get_logger",
]
