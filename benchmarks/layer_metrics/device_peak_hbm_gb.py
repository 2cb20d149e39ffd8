"""Peak device memory in use after the window, in GB (1e9 bytes), on the
fullest chip: ``memory_stats()["peak_bytes_in_use"]``."""


def read(run):
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
