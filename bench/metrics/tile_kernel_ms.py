"""Device milliseconds per iteration of the daemon's Pallas tile kernel:
the trace's operations with ``custom_call_target="tpu_custom_call"`` (the
only Pallas kernel on this path), over the window's iterations."""

from bench import tracing


def read(record):
    if record.trace is None:
        return None
    seconds = tracing.op_seconds(record.trace, tracing.PALLAS_MARK)
    if seconds <= 0.0:
        return None
    return 1e3 * seconds / sum(record.iterations)
