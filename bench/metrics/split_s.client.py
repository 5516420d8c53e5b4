"""Client protect, mask split (core/packing.py split_by_mask): the
program's span `protect.split`, the update vector copied to the host and
split there into the values to encrypt and the plaintext partition;
seconds per update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "protect.split")
