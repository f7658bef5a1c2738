"""Closed loop: the queue is topped up so that it never holds fewer
than ``capacity`` requests, from an endless stream that passes over
the mix's size set again and again, each pass in a fresh seeded
order."""
from chipbench import traffic


def prepare(mix: dict, seed: int, seconds: float, vocab: int):
    return traffic.ClosedStream(mix, seed, vocab)


def drive(stream, w) -> None:
    """Until the window closes: top the queue up, then one step."""
    while w.clock() < w.t_end:
        while w.eng.scheduler.queue_depth < w.mix["capacity"]:
            w.submit(stream.next(), w.clock())
        w.step()
