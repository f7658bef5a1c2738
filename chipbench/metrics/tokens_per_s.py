"""Tokens emitted inside the window over the window's length."""


def read(run):
    return run.tokens_in_window() / run.window_s
