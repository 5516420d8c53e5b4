"""The weighted accumulate's share of its roofline: the bytes the fold
must move (each ciphertext read, its accumulator chunk read and written)
at the chip's HBM bandwidth, over the accumulate's device time from the
trace.  A bytes bound: no integer-multiply peak is published for the
v5e."""
import counts

PROGRAM = r"_accum_chunks_graph"


def read(run):
    if run.trace is None:
        return None
    s, n = run.trace.program_seconds(PROGRAM)
    if not n or s <= 0:
        return None
    sh = run.shapes
    least = counts.fold_bytes(run.work["ct"], sh["n_poly"],
                              sh["n_limbs"]) / run.peaks.hbm_bytes_per_s
    return 100.0 * least / s
