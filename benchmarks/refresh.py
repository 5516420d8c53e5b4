"""Recompute derived roofline quantities from saved .hlo.gz files without
recompiling (estimator iteration tool).

  PYTHONPATH=src python -m benchmarks.refresh
"""
from __future__ import annotations

import gzip
import json
import os

from benchmarks import roofline as rf

ART = os.path.join(os.path.dirname(__file__), "artifacts")


def main():
    pk = rf.peaks(rf.DRYRUN_TARGET)
    n = 0
    for fn in sorted(os.listdir(ART)):
        if not fn.endswith(".json"):
            continue
        path = os.path.join(ART, fn)
        hlo = path.replace(".json", ".hlo.gz")
        if not os.path.exists(hlo):
            continue
        art = json.load(open(path))
        with gzip.open(hlo, "rt") as f:
            txt = f.read()
        colls = rf.parse_collectives(txt)
        fused = rf.parse_memory_traffic(txt)
        r = art["roofline"]
        fused = min(fused, r["bytes_accessed"]) if r["bytes_accessed"] else fused
        r["fused_bytes"] = fused
        r["memory_s"] = fused / pk.hbm_bytes_per_s
        r["memory_upper_s"] = r["bytes_accessed"] / pk.hbm_bytes_per_s
        r["wire_bytes"] = colls.wire_bytes
        r["collective_s"] = colls.wire_bytes / pk.ici_bytes_per_s
        terms = {"compute": r["compute_s"], "memory": r["memory_s"],
                 "collective": r["collective_s"]}
        r["dominant"] = max(terms, key=terms.get)
        r["step_s"] = max(terms.values())
        ideal = r["model_flops"] / pk.bf16_flops
        r["roofline_fraction"] = ideal / r["step_s"] if r["step_s"] else 0.0
        art["collectives"] = {"counts": colls.counts,
                              "by_op_bytes": colls.by_op}
        json.dump(art, open(path, "w"), indent=1)
        n += 1
    print(f"refreshed {n} artifacts from saved HLO")


if __name__ == "__main__":
    main()
