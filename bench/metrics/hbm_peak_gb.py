"""Peak device memory of the fullest chip after the window, in GB
(10**9 bytes), as the runtime's allocator reports it."""


def read(record):
    if not record.peak_bytes:
        return None
    return record.peak_bytes / 1e9
