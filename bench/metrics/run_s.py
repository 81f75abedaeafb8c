"""Seconds per analytics run: the window's whole time over the runs it
completed, each from the initial state to its result on the host."""


def read(record):
    return record.window_s / len(record.iterations)
