"""Seconds of ``Middleware.compile_step`` (tracing, lowering, and the
compile or the persistent-cache load)."""


def read(record):
    return record.compile_s
