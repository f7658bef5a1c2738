"""Device idle share of the traced window, from the profiler trace."""
from chipbench.readings import idle_share


def read(run):
    return idle_share(run)
