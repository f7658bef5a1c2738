"""Admission (B=1 prefill, join, sync): mean of the program's
``serve/admit`` spans in the traced window, ms."""
from chipbench.readings import span_mean_ms


def read(run):
    return span_mean_ms(run, "serve/admit")
