"""Device: the most device memory in use at once, in GB, on the fullest
chip (``memory_stats()["peak_bytes_in_use"]`` after the window)."""


def read(run):
    peak = max(run.memory)
    return peak / 1e9 if peak else None
