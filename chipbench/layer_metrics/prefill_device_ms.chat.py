"""Device time of one admission prefill: mean device-busy ms from the
start of each ``serve/prefill_call`` span to the end of its
``serve/first_token_wait`` (``chipbench.spans``)."""
from chipbench.spans import bracket_device_ms


def read(run):
    return bracket_device_ms(run, "serve/prefill_call",
                             "serve/first_token_wait")
