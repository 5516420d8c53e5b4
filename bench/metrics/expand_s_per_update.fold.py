"""Seeded expand in the server's ingest: the program's span
`wire.ingest.expand`, one per CT_CHUNK (seed check, the expand's
dispatch, the chunk queued for the flush); seconds per update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.ingest.expand")
