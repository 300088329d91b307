"""peak_hbm_gb: ``peak_bytes_in_use`` of the fullest chip after the
window, in GB (1e9 bytes)."""


def read(record):
    return record["peak_bytes"] / 1e9
