"""Device idle inside the program's ``serve/iteration`` spans, ms per
iteration; the split by innermost program span goes to the run's notes
(``chipbench.spans``)."""
from chipbench.spans import step_idle_ms


def read(run):
    return step_idle_ms(run)
