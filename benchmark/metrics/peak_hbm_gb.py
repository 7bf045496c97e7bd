"""peak_hbm_gb: the fullest chip's `peak_bytes_in_use` after the window,
in GB (1e9 bytes): reach, what still fits beside this cell."""


def read(record):
    return record["device"]["memory_peak_bytes"] / 1e9
