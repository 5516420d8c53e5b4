"""Device memory the window's own work holds: the largest memory_stats
bytes_in_use of the fullest chip that the role sampled at its boundaries
in the window (for the fold, after each update's ingest and after each
finalize), in GB.  None where the role samples nothing."""


def read(run):
    m = run.hbm_in_use_bytes
    return None if m is None else m / 1e9
