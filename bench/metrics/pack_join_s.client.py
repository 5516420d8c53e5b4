"""Wire pack, join: the program's span `wire.pack.join`, the
frames joined into the update's one blob; seconds per update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.pack.join")
