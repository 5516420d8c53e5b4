#!/usr/bin/env python
"""Docs checker: the CI docs job and the README bench-table generator.

Checks (default mode — exit nonzero on any failure):
  1. every intra-repo markdown link in README.md / DESIGN.md / ROADMAP.md
     resolves to an existing file or directory;
  2. the benchmark tables in README.md match what the checked-in
     BENCH_he.json / BENCH_agg_sharded.json / BENCH_uplink_sharded.json /
     BENCH_tune.json render to;
  3. the DESIGN.md §9.2 wire-spec appendix matches wire/format.py's
     version and derivation constants (the WIRE_SPEC marker);
  4. the README "Environment variables & flags" table's REPRO_HE_BACKEND
     row names every backend in kernels/ops.py BACKENDS (ref, pallas,
     pallas4, ...);
  5. the README quickstart snippets (first ```bash block after the
     "quickstart" heading AND after the "sharded uplink" heading) execute
     successfully, and the checked-in gold KATs match a fresh recompute
     (tools/gen_gold.py --check) — both skipped with --no-exec for fast
     local runs;
  6. the telemetry layer stays documented: README env-table rows for
     REPRO_OBS / REPRO_OBS_TRACE plus a tools/round_report.py pointer,
     and the DESIGN.md §11 obs section;
  7. the autotuner stays documented: README `REPRO_HE_TUNE_CACHE` row +
     `benchmarks.run tune` pointer, and the DESIGN.md §12 section;
  8. the selective pipeline stays documented: README `benchmarks.run
     selective` pointer + rendered BENCH_selective table + the
     REPRO_WIRE_VERSION env row, and the DESIGN.md §13 section (mask
     agreement -> partition -> wire -> merge, overhead accounting);
  9. the transcipher uplink stays documented: README `REPRO_UPLINK_MODE`
     env row + thin-client quickstart + `benchmarks.run uplink-hybrid` /
     tests/test_transcipher.py pointers, and the DESIGN.md §15 section
     (encode_centered / mod_lift contract, frame + escrow semantics).

`--write` regenerates the README tables in place between the
BENCH_TABLES_START/END markers instead of failing on drift.

Usage:
    python tools/check_docs.py            # full check (CI docs job)
    python tools/check_docs.py --no-exec  # links + tables + spec only
    python tools/check_docs.py --write    # refresh README bench tables
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "src"))   # for the wire-spec check
DOC_FILES = ("README.md", "DESIGN.md", "ROADMAP.md")
MARK_START = "<!-- BENCH_TABLES_START -->"
MARK_END = "<!-- BENCH_TABLES_END -->"

_LINK = re.compile(r"\[[^\]]*\]\(([^)#\s]+)(?:#[^)]*)?\)")


def check_links() -> list[str]:
    """Every relative markdown link must resolve inside the repo."""
    errors = []
    for doc in DOC_FILES:
        path = os.path.join(ROOT, doc)
        if not os.path.exists(path):
            errors.append(f"{doc}: file missing")
            continue
        text = open(path).read()
        for m in _LINK.finditer(text):
            target = m.group(1)
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            resolved = os.path.normpath(os.path.join(ROOT, target))
            if not resolved.startswith(ROOT):
                errors.append(f"{doc}: link escapes repo: {target}")
            elif not os.path.exists(resolved):
                errors.append(f"{doc}: broken link: {target}")
    return errors


def render_bench_tables() -> str:
    """Markdown tables from the checked-in BENCH json artifacts."""
    out = []

    he_path = os.path.join(ROOT, "BENCH_he.json")
    he = json.load(open(he_path))
    out.append(
        f"**Limb-fused engine vs per-limb dispatch baseline** "
        f"(`benchmarks/run.py he`; N={he['n_poly']}, L={he['n_limbs']}, "
        f"{he['n_clients']} clients, backend `{he['backend']}`):\n")
    out.append("| op | per-limb ms | fused ms | speedup |")
    out.append("|----|------------:|---------:|--------:|")
    for op, r in he["ops"].items():
        per = r.get("per_limb_ms")
        per_s = f"{per:.2f}" if per is not None else "—"
        spd = r.get("speedup")
        spd_s = f"{spd:.0f}x" if spd is not None else "—"
        out.append(f"| {op} | {per_s} | {r['fused_ms']:.2f} | {spd_s} |")
    out.append("")

    n4 = he["ntt4"]
    out.append(
        f"**Flat limb-grid NTT vs 4-step transpose NTT** "
        f"(`benchmarks/run.py ntt`; batch={n4['batch']}, "
        f"interpret={'yes' if n4['interpret'] else 'no'} — structure/"
        "dispatch tracking, not TPU lane behaviour; DESIGN.md §10):\n")
    out.append("| N | L | split n1 x n2 | fwd fused ms | fwd 4-step ms | "
               "inv fused ms | inv 4-step ms | bit-parity |")
    out.append("|---:|--:|---------------|-------------:|--------------:|"
               "-------------:|--------------:|:----------:|")
    for r in n4["rows"]:
        out.append(
            f"| {r['n_poly']} | {r['n_limbs']} | {r['split']} | "
            f"{r['fwd_fused_ms']:.2f} | {r['fwd_4step_ms']:.2f} | "
            f"{r['inv_fused_ms']:.2f} | {r['inv_4step_ms']:.2f} | "
            f"{'yes' if r['bit_parity'] else 'NO'} |")
    out.append("")

    ag_path = os.path.join(ROOT, "BENCH_agg_sharded.json")
    ag = json.load(open(ag_path))
    rows = [ag["per_devices"][k] for k in sorted(ag["per_devices"],
                                                key=lambda s: int(s))]
    r0 = rows[0]
    out.append(
        f"**Sharded vs single-device aggregation** "
        f"(`benchmarks/run.py agg-sharded`; N={r0['n_poly']}, "
        f"L={r0['n_limbs']}, {r0['n_clients']} clients x "
        f"{r0['n_chunks']} chunks, simulated host devices):\n")
    out.append("| devices | mesh (data x model) | weighted_sum single ms | "
               "weighted_sum sharded ms | stream ingest ms | "
               "launches/update | bit-parity |")
    out.append("|--------:|---------------------|----------------------:|"
               "------------------------:|-----------------:|"
               "----------------:|:----------:|")
    for r in rows:
        mesh = f"{r['mesh']['data']} x {r['mesh']['model']}"
        out.append(
            f"| {r['devices']} | {mesh} | "
            f"{r['weighted_sum_single_ms']:.2f} | "
            f"{r['weighted_sum_sharded_ms']:.2f} | "
            f"{r['stream_ingest_sharded_ms']:.0f} | "
            f"{r['launches_per_update']:.0f} | "
            f"{'yes' if r['sharded_parity'] else 'NO'} |")
    out.append("")

    up_path = os.path.join(ROOT, "BENCH_uplink_sharded.json")
    up = json.load(open(up_path))
    rows = [up["per_devices"][k] for k in sorted(up["per_devices"],
                                                 key=lambda s: int(s))]
    r0 = rows[0]
    out.append(
        f"**Sharded client uplink (seeded encrypt)** "
        f"(`benchmarks/run.py uplink-sharded`; N={r0['n_poly']}, "
        f"L={r0['n_limbs']}, {r0['n_chunks']} chunks, simulated host "
        "devices):\n")
    out.append("| devices | mesh (data x model) | seeded single ms | "
               "seeded sharded ms | pk single ms | pk sharded ms | "
               "seeded/full bytes | bit-parity |")
    out.append("|--------:|---------------------|-----------------:|"
               "------------------:|-------------:|--------------:|"
               "------------------:|:----------:|")
    for r in rows:
        mesh = f"{r['mesh']['data']} x {r['mesh']['model']}"
        out.append(
            f"| {r['devices']} | {mesh} | "
            f"{r['encrypt_seeded_single_ms']:.2f} | "
            f"{r['encrypt_seeded_sharded_ms']:.2f} | "
            f"{r['encrypt_pk_single_ms']:.2f} | "
            f"{r['encrypt_pk_sharded_ms']:.2f} | "
            f"{r['uplink_ratio']:.2f}x | "
            f"{'yes' if r['sharded_parity'] else 'NO'} |")
    out.append("")

    tn_path = os.path.join(ROOT, "BENCH_tune.json")
    tn = json.load(open(tn_path))
    plat = tn["provenance"]["platform"]
    out.append(
        f"**Autotuner: default vs swept launch configs** "
        f"(`benchmarks/run.py tune`; platform `{plat}`, "
        f"interpret={'yes' if tn['interpret'] else 'no'}; winners cached "
        "for `REPRO_HE_BACKEND=auto`, DESIGN.md §12):\n")
    out.append("| op | N | L | B | winner | config | default ms | "
               "tuned ms | speedup | candidates (pruned) |")
    out.append("|----|--:|--:|--:|--------|--------|-----------:|"
               "---------:|--------:|--------------------:|")
    for r in tn["rows"]:
        cfg = r["config"]
        bits = [f"block {cfg['block_b']}"]
        if cfg.get("ntt4_split"):
            bits.append(f"{cfg['ntt4_split'][0]}x{cfg['ntt4_split'][1]}")
        out.append(
            f"| {r['op']} | {r['n']} | {r['l']} | {r['b']} | "
            f"{r['backend']} | {', '.join(bits)} | "
            f"{r['default_ms']:.2f} | {r['tuned_ms']:.2f} | "
            f"{r['speedup']:.2f}x | {r['candidates']} ({r['pruned']}) |")
    out.append("")

    sel_path = os.path.join(ROOT, "BENCH_selective.json")
    sel = json.load(open(sel_path))
    big = sel["models"][-1]
    out.append(
        f"**Selective encryption end to end** (`benchmarks/run.py "
        f"selective`; {big['label']}, {big['n_params']/1e6:.1f}M params, "
        f"{big['n_clients']} clients, N={sel['ctx']['n_poly']}, "
        f"L={sel['ctx']['n_limbs']}, seeded uplink, plain codec "
        f"`{sel['plain_codec']}`, mesh {sel['mesh']['data']} x "
        f"{sel['mesh']['model']}; DESIGN.md §13):\n")
    out.append("| strategy | p | cts | uplink B/client | encrypt s | "
               "aggregate s | decrypt s | bytes vs p=1 | "
               "enc+agg time vs p=1 |")
    out.append("|----------|--:|----:|----------------:|----------:|"
               "-----------:|----------:|-------------:|"
               "--------------------:|")
    for r in big["rows"]:
        out.append(
            f"| {r['strategy']} | {r['p']:.2f} | {r['n_cts']} | "
            f"{r['uplink_B_per_client']:,} | {r['encrypt_s']:.3f} | "
            f"{r['aggregate_s']:.3f} | {r['decrypt_s']:.3f} | "
            f"{r['bytes_ratio_vs_p1']:.1f}x | "
            f"{r['time_ratio_vs_p1']:.1f}x |")
    out.append("")
    out.append(
        "**Extrapolated selective uplink at the paper's scales** (closed "
        "form from the measured per-chunk / per-plain-param wire costs "
        "above):\n")
    out.append("| model | params | p | est uplink MB/client | vs p=1 |")
    out.append("|-------|-------:|--:|---------------------:|-------:|")
    for r in sel["extrapolation"]:
        out.append(
            f"| {r['scale']} | {r['n_params']/1e6:.0f}M | {r['p']:.2f} | "
            f"{r['est_uplink_MB_per_client']:.1f} | "
            f"{r['bytes_ratio_vs_p1']:.1f}x |")
    out.append("")

    sv_path = os.path.join(ROOT, "BENCH_serve.json")
    sv = json.load(open(sv_path))
    c = sv["config"]
    out.append(
        f"**Async aggregation service** (`benchmarks/run.py serve`; "
        f"{c['n_clients']:,} simulated clients/round, quorum target "
        f"{c['target_clients']:,}, N={c['n_poly']}, L={c['n_limbs']}, "
        f"{c['n_chunks']} chunks, {c['blob_bytes']:,} B/update, backend "
        f"`{sv['backend']}`, worker-thread overlap on; DESIGN.md §14):\n")
    out.append("| round | accepted | stragglers dropped | folded | "
               "submit rate/s |")
    out.append("|------:|---------:|-------------------:|-------:|"
               "--------------:|")
    for r in sv["rows"]:
        out.append(
            f"| {r['round']} | {r['accepted']:,} | "
            f"{r['stragglers_dropped']:,} | {r['folded']:,} | "
            f"{r['submit_rate']:,.0f} |")
    out.append("")
    out.append(f"Sustained end to end (submit + fold + finalize, "
               f"{c['rounds']} rounds): "
               f"**{sv['sustained_updates_per_s']:,.0f} updates/s** "
               f"({sv['wall_s']:.1f}s wall).")
    out.append("")

    hy_path = os.path.join(ROOT, "BENCH_uplink_hybrid.json")
    hy = json.load(open(hy_path))
    out.append(
        f"**Thin-client transcipher uplink vs seeded CKKS** "
        f"(`benchmarks/run.py uplink-hybrid`; N={hy['n_poly']}, "
        f"L={hy['n_limbs']}, {hy['n_chunks']} chunks, delta 2^"
        f"{hy['delta_bits']}, backend `{hy['provenance']['backend']}`; "
        "client sends masked i64 coefficients + one escrowed keystream "
        "seed, server unmasks homomorphically — DESIGN.md §15):\n")
    out.append("| derive | seeded encrypt ms | masked pack ms | "
               "client speedup | seeded B | masked B | uplink ratio | "
               "bit-parity |")
    out.append("|--------|------------------:|---------------:|"
               "---------------:|---------:|---------:|-------------:|"
               ":----------:|")
    for name in ("fold_chunk", "ctr"):
        r = hy["per_derive"][name]
        out.append(
            f"| {name} | {r['seeded_encrypt_ms']:.2f} | "
            f"{r['masked_encrypt_ms']:.2f} | "
            f"{r['encrypt_speedup']:.2f}x | {r['seeded_B']:,} | "
            f"{r['masked_B']:,} | {r['uplink_ratio']:.2f}x | "
            f"{'yes' if r['bit_parity'] else 'NO'} |")
    return "\n".join(out) + "\n"


_WIRE_SPEC = re.compile(
    r"<!--\s*WIRE_SPEC\s+version=(\d+)\s+supported=([\d,]+)\s+"
    r"derives=([\d,]+)\s*-->")


def check_wire_spec() -> list[str]:
    """DESIGN.md §9.2 must agree with wire/format.py's constants.

    The appendix carries a machine-readable WIRE_SPEC marker; a version or
    derivation-id bump in code without the matching normative-spec edit
    fails the docs job (and vice versa)."""
    try:
        from repro.wire import format as wf
    except Exception as e:          # pragma: no cover - import environment
        return [f"DESIGN.md: cannot import repro.wire.format to verify "
                f"the wire spec: {e}"]
    full = open(os.path.join(ROOT, "DESIGN.md")).read()
    # scope every check to the §9.2 appendix itself, so gutting the
    # normative text cannot pass on phrases that also appear elsewhere
    sec = re.search(r"### §9\.2 .*?(?=\n## |\Z)", full, re.DOTALL)
    if not sec:
        return ["DESIGN.md: missing '### §9.2' wire-spec appendix section"]
    text = sec.group(0)
    m = _WIRE_SPEC.search(text)
    if not m:
        return ["DESIGN.md: missing WIRE_SPEC marker in the §9.2 appendix "
                "(<!-- WIRE_SPEC version=.. supported=.. derives=.. -->)"]
    errors = []
    if int(m.group(1)) != wf.VERSION:
        errors.append(f"DESIGN.md §9.2: spec version {m.group(1)} != "
                      f"wire/format.py VERSION {wf.VERSION}")
    spec_supported = tuple(int(x) for x in m.group(2).split(","))
    if spec_supported != tuple(wf.SUPPORTED_VERSIONS):
        errors.append(f"DESIGN.md §9.2: supported versions {spec_supported} "
                      f"!= wire/format.py {tuple(wf.SUPPORTED_VERSIONS)}")
    spec_derives = tuple(int(x) for x in m.group(3).split(","))
    if spec_derives != tuple(wf.DERIVES):
        errors.append(f"DESIGN.md §9.2: derive ids {spec_derives} != "
                      f"wire/format.py {tuple(wf.DERIVES)}")
    for needed in ("u8 derive", "fold_in", "chunk_offset + b",
                   "DERIVE_CTR"):
        if needed not in text:
            errors.append(f"DESIGN.md §9.2: normative appendix no longer "
                          f"spells out '{needed}'")
    return errors


def check_env_table() -> list[str]:
    """The README env-var table must keep pace with the backend registry:
    every name in kernels.ops.BACKENDS has to appear in the
    REPRO_HE_BACKEND row, so a new backend (e.g. pallas4) cannot land
    without its knob being documented."""
    try:
        from repro.kernels import ops
    except Exception as e:          # pragma: no cover - import environment
        return [f"README.md: cannot import repro.kernels.ops to verify the "
                f"REPRO_HE_BACKEND row: {e}"]
    text = open(os.path.join(ROOT, "README.md")).read()
    row = next((ln for ln in text.splitlines()
                if ln.startswith("| `REPRO_HE_BACKEND")), None)
    if row is None:
        return ["README.md: missing the `REPRO_HE_BACKEND` row in the "
                "'Environment variables & flags' table"]
    # whole-word match: "pallas4" in the row must not satisfy "pallas"
    words = set(re.findall(r"\w+", row))
    missing = [b for b in ops.BACKENDS if b not in words]
    if missing:
        return [f"README.md: REPRO_HE_BACKEND row does not mention "
                f"backend(s) {missing} (kernels/ops.py BACKENDS = "
                f"{list(ops.BACKENDS)})"]
    return []


def check_obs_docs() -> list[str]:
    """The telemetry layer must stay documented: README needs env-table
    rows for REPRO_OBS / REPRO_OBS_TRACE and a pointer at
    tools/round_report.py; DESIGN.md needs the §11 obs section."""
    errors = []
    readme = open(os.path.join(ROOT, "README.md")).read()
    for knob in ("REPRO_OBS", "REPRO_OBS_TRACE"):
        if not any(ln.startswith(f"| `{knob}") for ln in
                   readme.splitlines()):
            errors.append(f"README.md: missing the `{knob}` row in the "
                          "'Environment variables & flags' table")
    if "tools/round_report.py" not in readme:
        errors.append("README.md: telemetry docs no longer point at "
                      "tools/round_report.py")
    design = open(os.path.join(ROOT, "DESIGN.md")).read()
    if not re.search(r"^## §11 ", design, re.MULTILINE):
        errors.append("DESIGN.md: missing the '## §11' telemetry section "
                      "(repro/obs architecture + span taxonomy + overhead "
                      "policy)")
    return errors


def check_tune_docs() -> list[str]:
    """The autotuner must stay documented: README needs the
    `REPRO_HE_TUNE_CACHE` env row and a `benchmarks.run tune` pointer;
    DESIGN.md needs the §12 autotuner section (search space, cache key
    schema, pruning rule, bit-exactness argument)."""
    errors = []
    readme = open(os.path.join(ROOT, "README.md")).read()
    if not any(ln.startswith("| `REPRO_HE_TUNE_CACHE")
               for ln in readme.splitlines()):
        errors.append("README.md: missing the `REPRO_HE_TUNE_CACHE` row in "
                      "the 'Environment variables & flags' table")
    if "benchmarks.run tune" not in readme:
        errors.append("README.md: autotuner docs no longer point at "
                      "`benchmarks.run tune`")
    design = open(os.path.join(ROOT, "DESIGN.md")).read()
    sec = re.search(r"^## §12 .*?(?=\n## |\Z)", design,
                    re.MULTILINE | re.DOTALL)
    if not sec:
        errors.append("DESIGN.md: missing the '## §12' autotuner section")
        return errors
    for needed in ("block_b", "ntt4_split", "shape key",
                   "PRUNE_RATIO", "launch geometry"):
        if needed not in sec.group(0):
            errors.append(f"DESIGN.md §12: autotuner section no longer "
                          f"covers '{needed}'")
    return errors


def check_selective_docs() -> list[str]:
    """The selective pipeline must stay documented: README needs a
    `benchmarks.run selective` pointer and the `REPRO_WIRE_VERSION` env
    row (the wire knob the partitioned uplink rides on); DESIGN.md needs
    the §13 section covering mask agreement -> partition -> wire -> merge
    and the overhead accounting."""
    errors = []
    readme = open(os.path.join(ROOT, "README.md")).read()
    if "benchmarks.run selective" not in readme:
        errors.append("README.md: selective docs no longer point at "
                      "`benchmarks.run selective`")
    if not any(ln.startswith("| `REPRO_WIRE_VERSION")
               for ln in readme.splitlines()):
        errors.append("README.md: missing the `REPRO_WIRE_VERSION` row in "
                      "the 'Environment variables & flags' table")
    design = open(os.path.join(ROOT, "DESIGN.md")).read()
    sec = re.search(r"^## §13 .*?(?=\n## |\Z)", design,
                    re.MULTILINE | re.DOTALL)
    if not sec:
        errors.append("DESIGN.md: missing the '## §13' selective-pipeline "
                      "section")
        return errors
    for needed in ("agree_sensitivity", "build_mask", "MaskPartition",
                   "plain_codec", "merge_by_mask", "overhead"):
        if needed not in sec.group(0):
            errors.append(f"DESIGN.md §13: selective section no longer "
                          f"covers '{needed}'")
    return errors


def check_serve_docs() -> list[str]:
    """The aggregation service must stay documented: README needs the
    'Aggregation service quickstart' section with a runnable snippet and
    `benchmarks.run serve` / tests/test_serve.py pointers; DESIGN.md
    needs the §14 section covering the state machine, quorum semantics,
    crash consistency, and the fault taxonomy."""
    errors = []
    readme = open(os.path.join(ROOT, "README.md")).read()
    if not re.search(r"^## Aggregation service quickstart", readme,
                     re.MULTILINE):
        errors.append("README.md: missing the 'Aggregation service "
                      "quickstart' section")
    if "benchmarks.run serve" not in readme:
        errors.append("README.md: service docs no longer point at "
                      "`benchmarks.run serve`")
    if "tests/test_serve.py" not in readme:
        errors.append("README.md: service docs no longer point at "
                      "tests/test_serve.py")
    design = open(os.path.join(ROOT, "DESIGN.md")).read()
    sec = re.search(r"^## §14 .*?(?=\n## |\Z)", design,
                    re.MULTILINE | re.DOTALL)
    if not sec:
        errors.append("DESIGN.md: missing the '## §14' aggregation-service "
                      "section")
        return errors
    for needed in ("OPEN", "SEALED", "FOLDING", "FAILED", "min_clients",
                   "REFOLD", "at-least-once", "SimulatedCrash",
                   "export_state", "garbage"):
        if needed not in sec.group(0):
            errors.append(f"DESIGN.md §14: service section no longer "
                          f"covers '{needed}'")
    return errors


def check_transcipher_docs() -> list[str]:
    """The transcipher uplink must stay documented: README needs the
    `REPRO_UPLINK_MODE` env row, the thin-client quickstart section, and
    `benchmarks.run uplink-hybrid` / tests/test_transcipher.py pointers;
    DESIGN.md needs the §15 section covering the encode_centered /
    mod_lift exactness contract, provisioning, and the frame + escrow
    ingest semantics."""
    errors = []
    readme = open(os.path.join(ROOT, "README.md")).read()
    if not any(ln.startswith("| `REPRO_UPLINK_MODE")
               for ln in readme.splitlines()):
        errors.append("README.md: missing the `REPRO_UPLINK_MODE` row in "
                      "the 'Environment variables & flags' table")
    if not re.search(r"^## Thin-client transcipher uplink quickstart",
                     readme, re.MULTILINE):
        errors.append("README.md: missing the 'Thin-client transcipher "
                      "uplink quickstart' section")
    if "benchmarks.run uplink-hybrid" not in readme:
        errors.append("README.md: transcipher docs no longer point at "
                      "`benchmarks.run uplink-hybrid`")
    if "tests/test_transcipher.py" not in readme:
        errors.append("README.md: transcipher docs no longer point at "
                      "tests/test_transcipher.py")
    design = open(os.path.join(ROOT, "DESIGN.md")).read()
    sec = re.search(r"^## §15 .*?(?=\n## |\Z)", design,
                    re.MULTILINE | re.DOTALL)
    if not sec:
        errors.append("DESIGN.md: missing the '## §15' transcipher-uplink "
                      "section")
        return errors
    for needed in ("encode_centered", "mod_lift", "MASKED_CHUNK",
                   "TRANSCIPHER_SEED", "ClientMaterials", "ServerMaterials",
                   "provision", "escrow", "uplink_a_seed",
                   "add_transcipher_materials"):
        if needed not in sec.group(0):
            errors.append(f"DESIGN.md §15: transcipher section no longer "
                          f"covers '{needed}'")
    return errors


def check_or_write_tables(write: bool) -> list[str]:
    path = os.path.join(ROOT, "README.md")
    text = open(path).read()
    if MARK_START not in text or MARK_END not in text:
        return [f"README.md: missing {MARK_START}/{MARK_END} markers"]
    head, rest = text.split(MARK_START, 1)
    _, tail = rest.split(MARK_END, 1)
    try:
        rendered = MARK_START + "\n" + render_bench_tables() + MARK_END
    except (OSError, KeyError, ValueError) as e:
        # a missing BENCH json / section is a docs error, not a traceback
        # (e.g. BENCH_he.json regenerated by `run he` alone lacks 'ntt4' —
        # run `python -m benchmarks.run ntt` too)
        return [f"README.md: cannot render bench tables from the checked-in "
                f"BENCH json artifacts: {e!r}"]
    new = head + rendered + tail
    if new == text:
        return []
    if write:
        open(path, "w").write(new)
        print("README.md bench tables refreshed")
        return []
    return ["README.md: bench tables out of date with BENCH json "
            "(run `python tools/check_docs.py --write`)"]


def _run_snippet(heading: str) -> list[str]:
    """Extract and execute the first ```bash block after `heading`."""
    text = open(os.path.join(ROOT, "README.md")).read()
    m = re.search(heading + r".*?```bash\n(.*?)```", text,
                  re.IGNORECASE | re.DOTALL)
    if not m:
        return [f"README.md: no ```bash block found after '{heading}'"]
    script = m.group(1)
    with tempfile.NamedTemporaryFile("w", suffix=".sh", delete=False) as f:
        f.write("set -euo pipefail\n" + script)
        name = f.name
    try:
        proc = subprocess.run(["bash", name], cwd=ROOT, capture_output=True,
                              text=True, timeout=900)
    finally:
        os.unlink(name)
    if proc.returncode != 0:
        return [f"README '{heading}' snippet failed "
                f"(exit {proc.returncode}):\n{proc.stdout}\n{proc.stderr}"]
    print(f"README '{heading}' snippet OK: "
          f"{proc.stdout.strip().splitlines()[-1]}")
    return []


def run_quickstart() -> list[str]:
    """Execute the README snippets: the encrypted-averaging quickstart,
    the sharded-uplink quickstart, the aggregation-service quickstart,
    and the thin-client transcipher quickstart (each is the first
    ```bash block after its heading)."""
    return (_run_snippet(r"quickstart") + _run_snippet(r"sharded uplink")
            + _run_snippet(r"aggregation service")
            + _run_snippet(r"thin-client transcipher"))


def check_gold_kats() -> list[str]:
    """The checked-in gold KATs (tests/golden/ckks_kats.json) must match a
    fresh recompute — a code change that silently moves the known answers
    fails the docs job, not just the test suite."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "gen_gold.py"),
         "--check"], cwd=ROOT, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        return [f"gold KATs drifted (tools/gen_gold.py --check):\n"
                f"{proc.stdout}\n{proc.stderr}"]
    print(proc.stdout.strip().splitlines()[-1])
    return []


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--write", action="store_true",
                    help="refresh README bench tables instead of checking")
    ap.add_argument("--no-exec", action="store_true",
                    help="skip executing the README quickstart snippet")
    args = ap.parse_args()

    errors = check_links()
    errors += check_or_write_tables(write=args.write)
    errors += check_wire_spec()
    errors += check_env_table()
    errors += check_obs_docs()
    errors += check_tune_docs()
    errors += check_selective_docs()
    errors += check_serve_docs()
    errors += check_transcipher_docs()
    if not args.no_exec and not args.write:
        errors += run_quickstart()
        errors += check_gold_kats()
    for e in errors:
        print(f"DOCS ERROR: {e}", file=sys.stderr)
    if not errors:
        print("docs check passed")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
