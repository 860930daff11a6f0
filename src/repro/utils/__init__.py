from repro.utils.compile_cache import enable_compile_cache
from repro.utils.pytree import (
    flatten_with_paths,
    map_with_paths,
    path_str,
    tree_bytes,
    tree_params,
)

__all__ = [
    "enable_compile_cache",
    "flatten_with_paths",
    "map_with_paths",
    "path_str",
    "tree_bytes",
    "tree_params",
]
