"""The weighted accumulate of the fold (kernels/he_agg.py, kernels/ref.py):
device time of its jitted program, `_accum_chunks_graph`, read from the
trace, whatever backend implements it; ms per update."""

PROGRAM = r"_accum_chunks_graph"


def read(run):
    if run.trace is None or not run.work.get("updates"):
        return None
    s, n = run.trace.program_seconds(PROGRAM)
    return 1e3 * s / run.work["updates"] if n else None
