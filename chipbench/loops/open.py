"""Open loop: requests arrive on a schedule fixed before the window
opens (Poisson at ``rate_per_s``), whether or not earlier ones have
finished; each is due at its scheduled arrival."""
from chipbench import traffic


def prepare(mix: dict, seed: int, seconds: float, vocab: int):
    return traffic.open_schedule(mix, seed, seconds, vocab)


def drive(sched, w) -> None:
    """Submit what is due, step while work is pending, else wait for
    the next arrival."""
    i = 0
    while True:
        now = w.clock()
        if now >= w.t_end:
            break
        while i < len(sched) and w.t_open + sched[i].arrival_s <= now:
            w.submit(sched[i], w.t_open + sched[i].arrival_s)
            i += 1
        if w.eng.scheduler.pending:
            w.step()
        else:
            nxt = w.t_open + sched[i].arrival_s if i < len(sched) \
                else w.t_end
            w.wait(min(nxt, w.t_end))
