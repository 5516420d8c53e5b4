"""Wire pack, ciphertext frames: the program's span
`wire.pack.ct_frames`, each chunk serialized and framed; seconds per
update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.pack.ct_frames")
