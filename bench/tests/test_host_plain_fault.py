"""The client comparison catches an altered plaintext partition where the
program hands the pack a host array: the fault is planted on a host copy of
the plaintext on its way to the wire pack, and the smoke client cell must
report `correct` false for every unit."""
from __future__ import annotations

import dataclasses

import numpy as np

import smoke
import run
import spec

SEED = 2 ** 33 + 17
LIMITS = {"smoke.client": {"enc_gap": {"limit": 1e-3},
                           "plain_gap": {"limit": 0.0},
                           "loss_gap": {"limit": 2e-4},
                           "grad_gap": {"limit": 0.02},
                           "change_gap": {"limit": 0.1}}}


def test_client_host_plain_altered_is_not_correct(tmp_path, monkeypatch):
    from repro.wire import stream as ws

    pack = ws.pack_update_frames

    def altered(upd, **kw):
        plain = np.array(upd.plain)
        plain[0] += 1e-3
        return pack(dataclasses.replace(upd, plain=plain), **kw)

    monkeypatch.setattr(ws, "pack_update_frames", altered)
    root = smoke.make_root(tmp_path, LIMITS)
    r = run.run_cell(spec.cell("smoke.client", root), SEED, 0.5, False,
                     smoke.CPU_DEVICE)
    assert r["correct"] is False and r["failed"] == r["attempted"] >= 1
    assert r["checks"]["plain_gap"]["value"] > 0
