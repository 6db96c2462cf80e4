"""Where the benchmark keeps JAX's persistent compilation cache."""

import os


def place(root: str) -> str:
    """Keep the cache at ``<root>/.jax_cache``, a fixed path inside the
    checkout (the path is part of what the cache is found by, and two
    checkouts share nothing), with no size limit, so that the largest
    programs (about 0.5 GB of code each) are kept too. Call before JAX
    is imported: JAX reads these at import."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    return path
