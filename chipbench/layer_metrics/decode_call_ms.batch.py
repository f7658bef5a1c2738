"""Host time to enqueue one batched decode: mean of the program's
``serve/decode_call`` spans in the traced window, ms."""
from chipbench.readings import span_mean_ms


def read(run):
    return span_mean_ms(run, "serve/decode_call")
