"""Wire pack, fetch (wire/stream.py pack_update_frames): the
program's span `wire.pack.fetch`, the update's ciphertexts and plaintext
partition copied from the device to the host; seconds per update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.pack.fetch")
