"""Device time of one batched decode: mean device-busy ms from the
start of each ``serve/decode_call`` span to the end of its
``serve/decode_wait`` (``chipbench.spans``)."""
from chipbench.spans import bracket_device_ms


def read(run):
    return bracket_device_ms(run, "serve/decode_call", "serve/decode_wait")
