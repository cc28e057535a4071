"""peak_hbm_gb: the device allocator's peak bytes in use after the window
(``memory_stats()["peak_bytes_in_use"]``, fullest chip), in GB (1e9).
It guards the narrow-index memory claim: a change that keeps dequantized
weights in HBM shows here."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
