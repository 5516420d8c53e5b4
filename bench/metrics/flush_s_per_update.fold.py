"""The ingest's flush (wire/stream.py StreamIngest.flush): the
program's span `wire.flush` (chunks stacked, the accumulate dispatched,
the accumulator's bookkeeping); seconds per update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.flush")
