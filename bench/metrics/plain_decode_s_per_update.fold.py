"""Plaintext decode in the server's ingest: the program's span
`wire.ingest.plain_decode` (the PLAIN_SEGMENT parsed, dequantized and
its shape checked); seconds per update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.ingest.plain_decode")
