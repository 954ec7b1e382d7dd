"""Peak device memory of the fullest chip after the window, in MB
(10^6 bytes), from ``memory_stats()["peak_bytes_in_use"]``."""


def read(rec):
    if not rec.memory_peak_bytes:
        return None
    return rec.memory_peak_bytes / 1e6
