"""Wire pack, plaintext segment: the program's span
`wire.pack.plain`, the partition quantized and serialized; seconds per
update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.pack.plain")
