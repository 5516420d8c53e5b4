"""Client protect (core/secure_agg.py client_protect_seeded,
core/ckks/cipher.py): the harness's span around the aggregator call inside
protect_and_pack, ended once the ciphertexts are on the device; seconds
per update."""


def read(run):
    s = run.spans.get("encrypt")
    n = run.work.get("updates")
    return s / n if s and n else None
