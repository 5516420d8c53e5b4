"""Local training (fl/client.py FLClient.local_train): the harness's span
around each call, ended once the local model is on the device; seconds per
update."""


def read(run):
    s = run.spans.get("train")
    n = run.work.get("updates")
    return s / n if s and n else None
