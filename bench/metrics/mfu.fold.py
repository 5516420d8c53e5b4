"""The whole fold step's share of the chip's peak: the bytes the fold must
move for every ciphertext of the window at the chip's HBM bandwidth, over
the window.  The fold does no matrix FLOPs; this bytes share plays the
role an mfu plays for a model step."""
import counts


def read(run):
    if not run.work.get("updates"):
        return None
    sh = run.shapes
    least = counts.fold_bytes(run.work["ct"], sh["n_poly"],
                              sh["n_limbs"]) / run.peaks.hbm_bytes_per_s
    return 100.0 * least / run.window_s
