"""90th percentile over the requests due in the window of first-token
time minus scheduled arrival; one unanswered at the close counts at
the close.  (A window holds ~100 requests, so the 90th is the highest
percentile with ten requests beyond it.)"""
from chipbench.readings import percentile


def read(run):
    ttft = []
    for rid in run.due:
        r = run.recs[rid]
        first = r.times[0] if r.times and r.times[0] <= run.t_close \
            else run.t_close
        ttft.append(1e3 * (first - r.due))
    return percentile(ttft, 90)
