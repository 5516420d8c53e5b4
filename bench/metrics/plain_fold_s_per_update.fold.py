"""Plaintext fold in the server's ingest: the program's span
`wire.ingest.plain_fold` (the f32 weighted add of the segment into the
host accumulator); seconds per update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.ingest.plain_fold")
