"""Frame parsing in the server's ingest (wire/stream.py
StreamIngest.ingest): the program's span `wire.ingest.parse`, one per
frame (the frame cut, header reads and each CT_CHUNK's deserialize);
seconds per update."""
import program_spans


def read(run):
    return program_spans.per_update(run, "wire.ingest.parse")
