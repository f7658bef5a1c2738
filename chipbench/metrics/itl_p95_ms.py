"""95th percentile over every gap between consecutive tokens of a
request, inside the window."""
from chipbench.readings import percentile


def read(run):
    gaps = []
    for r in run.recs.values():
        t = [x for x in r.times if x <= run.t_close]
        gaps += [1e3 * (b - a) for a, b in zip(t, t[1:])]
    return percentile(gaps, 95)
