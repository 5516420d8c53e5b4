"""The local steps' model FLOPs (counts.py: 6 per parameter per token plus
causal attention; recomputation not counted) over the window times the
chip's bf16 peak.  None for a model family that counts.py has no FLOPs
for."""
import counts


def read(run):
    sh = run.shapes
    tokens = run.work.get("tokens")
    if not tokens or sh["family"] != "dense":
        return None
    flops = counts.dense_train_flops(sh["params"], tokens, sh["n_layers"],
                                     sh["d_model"], sh["seq_len"])
    return 100.0 * flops / (run.window_s * run.peaks.bf16_flops)
