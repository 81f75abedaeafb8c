"""Share of the traced window in which no operation ran on the device:
1 - (union of the chips' operation intervals) / window, in %."""

from bench import tracing


def read(record):
    if record.trace is None:
        return None
    return 100.0 * (1.0 - tracing.busy_s(record.trace)
                    / record.trace.window_s)
