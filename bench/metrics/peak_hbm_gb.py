"""Peak device memory of the whole process, read after the window
(memory_stats peak_bytes_in_use of the fullest chip), in GB: it covers the
set-up too, so it stands for a cell whose set-up runs the window's own
unit."""


def read(run):
    m = run.memory_peak_bytes
    return None if m is None else m / 1e9
