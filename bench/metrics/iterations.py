"""Mean drive-loop iterations per run in the window (``Result.iterations``)."""


def read(record):
    return sum(record.iterations) / len(record.iterations)
