"""Server ingest (wire/stream.py StreamIngest.ingest): the harness's span
around each call, ended once the accumulator holds the update; seconds
per update."""


def read(run):
    s = run.spans.get("ingest")
    n = run.work.get("updates")
    return s / n if s and n else None
