"""Seconds of the host build: the ``Middleware`` constructor (partition,
blocks, CSR compaction, placement), until the placed tensors are ready."""


def read(record):
    return record.build_s
