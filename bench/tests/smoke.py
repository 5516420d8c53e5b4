"""A smoke-size copy of the benchmark's data for CPU tests: the same
harness code over configurations, traffic and limits small enough for the
CPU, in a directory laid out like the checkout."""
from __future__ import annotations

import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_CKKS = {"n_poly": 256, "n_limbs": 2, "delta_bits": 26,
              "max_prime_bits": 30}


def qwen_smoke() -> dict:
    with open(os.path.join(BENCH, "configs", "qwen1.5-0.5b-silo.json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=4, vocab_size=257,
             encrypted_share=0.05)
    c["deployment"] = dict(c["deployment"], ckks=dict(SMOKE_CKKS))
    return c


def _traffic(name: str, **small) -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return dict(json.load(f), **small)


def make_root(tmp, limits: dict | None = None) -> str:
    """A checkout-like directory whose BENCHMARK.json names the smoke cells
    `smoke.fold` and `smoke.client` (config `qwen-smoke`)."""
    root = os.path.join(str(tmp), "root")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b = copy.deepcopy(b)
    b["configs"] = [{"name": n, "source": "smoke",
                     "file": f"bench/configs/{n}.json", "reduced": [],
                     "why": "smoke"} for n in ("qwen-smoke",)]
    b["workloads"] = [
        {"name": "smoke.fold", "config": "qwen-smoke",
         "traffic": "silo-fold", "chips": 1, "why": "smoke"},
        {"name": "smoke.client", "config": "qwen-smoke",
         "traffic": "silo-client-smoke", "chips": 1, "why": "smoke"}]
    roles = {"smoke.fold": "fold", "smoke.client": "client"}
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            role = "fold" if any("fold" in w for w in m["workloads"]) \
                else "client"
            m["workloads"] = [w for w, r in roles.items() if r == role]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    files = {"configs/qwen-smoke": qwen_smoke(),
             "traffic/silo-fold": _traffic("silo-fold"),
             "traffic/silo-client-smoke": _traffic("silo-client", batch=2,
                                                   seq_len=16)}
    for name, data in files.items():
        with open(os.path.join(root, "bench", name + ".json"), "w") as f:
            json.dump(data, f)
    for name, lim in (limits or {}).items():
        with open(os.path.join(root, "bench", "limits", name + ".json"),
                  "w") as f:
            json.dump(lim, f)
    return root


CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
