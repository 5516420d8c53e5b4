"""The silo server's aggregation rate: ciphertexts of the silo updates
ingested from wire bytes into the device accumulator in the window's whole
rounds, each finalized inside the window, over the window's whole
length."""


def read(run):
    if not run.work.get("updates"):
        return None
    return run.work["ct"] / run.window_s
