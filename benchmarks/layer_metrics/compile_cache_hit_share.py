"""Share of this process's compile requests that JAX's persistent cache
served. 1.0 in every run of a cell after the first in a checkout."""
LAYER = "compile_cache"
SOURCE = "program_counter"
UNIT = "fraction"
BETTER = "higher"
MOVES = "setup_s"


def read(run):
    if not run.cache_requests:
        return None
    return run.cache_hits / run.cache_requests
