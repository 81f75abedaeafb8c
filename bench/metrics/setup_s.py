"""Seconds from process start until the window opens: imports, graph
generation, host build, compile and the warm-up run."""


def read(record):
    return record.setup_s
