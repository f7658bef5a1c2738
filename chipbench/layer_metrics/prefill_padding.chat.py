"""Padding among the tokens the window's admissions prefilled, percent:
``padded_to`` less ``prompt_tokens`` over ``padded_to``, summed over the
program's ``serve/admit`` spans."""
from chipbench.spans import padding_share


def read(run):
    return padding_share(run)
