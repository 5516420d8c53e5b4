"""A silo's cost for one round's update: the window's whole length over
the updates completed in it (local training, protect, pack to wire
bytes)."""


def read(run):
    if not run.work.get("updates"):
        return None
    return run.window_s / run.work["updates"]
