"""Reproduce the paper's key trade-off curves on a real model:
overhead vs selection ratio (Table 7 / Figure 7) and the privacy-budget
advantage of sensitivity-ordered selection (Remarks 3.12-3.14), using an
actual fine-tune + sensitivity map from trained LM clients.

The heavy lifting lives in benchmarks/selective.py (the `benchmarks.run
selective` mode): this example reuses its client half
(`fine_tune_and_sense`) for the sensitivity map and adds the DP-advantage
table on top.  For the full measured pipeline sweep (wire bytes, sharded
aggregation wall time, BENCH_selective.json) run

    PYTHONPATH=src python -m benchmarks.run selective [--smoke]

    PYTHONPATH=src python examples/selective_encryption_sweep.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))   # repo root: benchmarks/

from benchmarks.selective import fine_tune_and_sense, model_cfgs
from repro.core import dp
from repro.core.ckks import params as ckks_params
from repro.core.secure_agg import AggregatorConfig, SelectiveHEAggregator
from repro.launch import compile_cache


def main():
    compile_cache.enable()
    (_, cfg), = model_cfgs(smoke=True)
    print("fine-tuning 2 clients + computing per-parameter sensitivity "
          f"maps ({cfg.param_count()/1e3:.0f}k params)...")
    task = fine_tune_and_sense(cfg)
    sens = np.average(np.stack(task["sens_maps"]), axis=0,
                      weights=task["weights"])
    print(f"mean local loss {task['loss']:.3f}; sensitivity: "
          f"min={sens.min():.2e} max={sens.max():.2e} "
          f"p99/p50={np.percentile(sens,99)/max(np.percentile(sens,50),1e-12):.1f} "
          "(heavily imbalanced, Figure 5)")

    params = task["global_params"]
    ctx = ckks_params.make_context(n_poly=2048, n_limbs=2, delta_bits=24)
    print(f"\n{'p':>5} {'cts':>6} {'comm_MB':>8} {'ratio':>6} "
          f"{'eps_sel/J':>10} {'eps_rnd/J':>10}")
    j = dp.epsilon_all_plaintext(sens, b=1.0)
    for p in (0.0, 0.05, 0.1, 0.3, 0.5, 1.0):
        agg = SelectiveHEAggregator.build(
            ctx, params, sens, AggregatorConfig(p_ratio=p))
        rep = agg.overhead_report()
        adv = dp.selection_advantage(sens, p, b=1.0) if 0 < p < 1 else None
        es = adv["eps_selective"] / j if adv else (1.0 if p == 0 else 0.0)
        er = adv["eps_random"] / j if adv else (1.0 if p == 0 else 0.0)
        print(f"{p:5.2f} {rep['n_ciphertexts']:6d} "
              f"{rep['bytes_total']/1e6:8.2f} {rep['comm_ratio']:6.2f} "
              f"{es:10.3f} {er:10.3f}")
    print("\nsensitivity-ordered selection spends quadratically less "
          "privacy budget than random selection at equal overhead "
          "(Remark 3.14).")


if __name__ == "__main__":
    main()
