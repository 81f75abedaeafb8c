"""Device-idle milliseconds per iteration whose innermost host span is
the drive loop's ``plug.iteration`` or one of its children (poll,
dispatch, fetch): the per-iteration round trip an on-device loop would
remove (bench/spans.py)."""

from bench import spans


def read(record):
    if record.trace is None:
        return None
    idle = spans.idle_by_span(record.trace)
    if idle is None:
        return None
    seconds = sum(idle.get(name, 0.0) for name in spans.ITERATION)
    return 1e3 * seconds / sum(record.iterations)
