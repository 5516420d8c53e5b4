"""Wire pack (wire/stream.py pack_update_frames, with the seed compression
before it): protect_and_pack's span less the encrypt span inside it;
seconds per update."""


def read(run):
    p, e = run.spans.get("protect"), run.spans.get("encrypt")
    n = run.work.get("updates")
    return (p - e) / n if p and e and n else None
