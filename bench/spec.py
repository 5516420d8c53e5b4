"""Everything the harness finds by name: BENCHMARK.json, a cell's
configuration file, its traffic file, its limits file and the reader of
each metric.

A configuration is `configs/<config>.json`, a traffic mix
`traffic/<traffic>.json`, the correctness limits of a cell
`limits/<workload>.json`, and a metric `metrics/<metric>.py` with a
`read(run)` function; a metric `<quantity>.<part>` with no file of its own
is read by `metrics/<quantity>.py`.  Adding a cell, a configuration or a metric is adding
files and entries; no code here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict          # the configuration file as it is run
    traffic: dict         # the traffic file
    limits: dict          # {check name: {"limit": x, ...}}
    end_to_end: tuple     # metric entries of BENCHMARK.json for this cell
    per_layer: tuple


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _for_cell(metrics, name: str) -> tuple:
    return tuple(m for m in metrics
                 if "workloads" not in m or name in m["workloads"])


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of <root>/BENCHMARK.json with its files loaded."""
    b = benchmark(root)
    by_name = {w["name"]: w for w in b["workloads"]}
    if name not in by_name:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in b["configs"]}[w["config"]]
    here = os.path.join(root, "bench")
    lim_path = os.path.join(here, "limits", name + ".json")
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=_load_json(os.path.join(root, cfg_entry["file"])),
                traffic=_load_json(os.path.join(here, "traffic",
                                                w["traffic"] + ".json")),
                limits=(_load_json(lim_path) if os.path.exists(lim_path)
                        else {}),
                end_to_end=_for_cell(b["end_to_end"], name),
                per_layer=_for_cell(b["per_layer"], name))


def reader(metric: str):
    """The `read(run)` function of bench/metrics/<metric>.py, or of
    bench/metrics/<quantity>.py for a metric <quantity>.<part> without a
    file of its own."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    if not os.path.exists(path):
        path = os.path.join(BENCH, "metrics", metric.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# a configuration file -> the program's ModelConfig
# ---------------------------------------------------------------------------


def model_config(config: dict, name: str):
    """The program's ModelConfig for a configuration file, key by key from
    the published config.json names; an unknown model_type is an error."""
    from repro.models.config import ModelConfig

    tr = config["training"]
    common = dict(name=name, dtype=tr["compute_dtype"],
                  param_dtype=tr["param_dtype"], remat=bool(tr["remat"]))
    mt = config["model_type"]
    if mt == "qwen2":
        if config["hidden_act"] != "silu" or config["use_sliding_window"]:
            raise ValueError("qwen2 configs are run with SwiGLU and full "
                             "attention only")
        return ModelConfig(
            family="dense", n_layers=config["num_hidden_layers"],
            d_model=config["hidden_size"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            d_ff=config["intermediate_size"], vocab=config["vocab_size"],
            qkv_bias=True, tie_embeddings=config["tie_word_embeddings"],
            rope_theta=float(config["rope_theta"]), **common)
    raise ValueError(f"no mapping for model_type {mt!r}")
