"""CPU rehearsal of every cell's code path at smoke size: the traffic's
set-up and units through the same harness code as the chip cells, the
comparison with the reference, and that the comparison fails under the
control (a lower precision in the program's place) and under each fault a
cell can have, planted in the program underneath the timed path."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import smoke
import calibrate
import check
import run
import spec

SEED = 2 ** 33 + 17

# smoke-size limits, set from smoke readings: sound runs read enc_gap
# ~3e-5, loss_gap ~4e-5, grad_gap ~4e-4, change_gap ~0.01; the control
# reads enc_gap >= 4e-3, loss_gap 6e-4, grad_gap 0.2, change_gap 1
LIMITS = {
    "smoke.fold": {"enc_gap": {"limit": 1e-3}, "plain_gap": {"limit": 0.0}},
    "smoke.client": {"enc_gap": {"limit": 1e-3}, "plain_gap": {"limit": 0.0},
                     "loss_gap": {"limit": 2e-4}, "grad_gap": {"limit": 0.02},
                     "change_gap": {"limit": 0.1}}
}
CELLS = list(LIMITS)
CLIENTS = ["smoke.client"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.make_root(tmp_path_factory.mktemp("bench"), LIMITS)


def _run(root, name, seed=SEED):
    return run.run_cell(spec.cell(name, root), seed, 0.5, False,
                        smoke.CPU_DEVICE)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(root, name):
    r = _run(root, name)
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(LIMITS[name])
    assert set(r["metrics"]) == {"setup_s", "fold_ct_per_s"
                                 if name == "smoke.fold"
                                 else "client_update_s"}


def test_cell_from_data_files_alone(root):
    """The smoke cells exist only as BENCHMARK.json entries, a config JSON
    and traffic JSONs: no harness code names them."""
    c = spec.cell("smoke.client", root)
    assert c.config["hidden_size"] == 64
    assert c.traffic["role"] == "client" and c.traffic["batch"] == 2
    assert [m["name"] for m in c.end_to_end] == ["client_update_s",
                                                  "setup_s"]
    for m in c.per_layer:
        assert callable(spec.reader(m["name"]))


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(root, name):
    r = calibrate.readings(spec.cell(name, root), SEED)
    lim = LIMITS[name]
    # the program's own f16 path for the plaintext partition
    assert not check.judge(r["program_f16_codec"], lim)[0]
    assert r["program_f16_codec"]["plain_gap"] > 0
    # the reference in the next lower precision in the program's place:
    # the encrypted values in bfloat16, and for training, the steps in
    # float8, each fail at least one number
    ctrl = {k: v for k, v in r["control"].items() if k in lim}
    assert ctrl["enc_gap"] > lim["enc_gap"]["limit"]
    train = {k: v for k, v in ctrl.items() if k != "enc_gap"}
    assert not train or any(v > lim[k]["limit"] for k, v in train.items())


# -- faults planted in the program --------------------------------------------


def _fold_faults(monkeypatch, fault):
    from repro.wire import stream as ws

    orig = ws._accum_chunks_graph

    def graph(ctx, token, accs, cts, w):
        out = orig(ctx, token, accs, cts, w)
        if fault == "state_unchanged":
            return accs
        if fault == "half_left_out":
            k = accs.shape[0] // 2
            return out.at[k:].set(accs[k:])
        return out.at[0, 0, 0, 0].add(jnp.uint32(1 << 20))

    if fault == "samples_misread":
        meta = ws.peek_update_meta

        def misread(blob):
            m = meta(blob)
            return dataclasses.replace(m, n_samples=m.n_samples + 1000
                                       if m.cid == 0 else m.n_samples)

        monkeypatch.setattr(ws, "peek_update_meta", misread)
    elif fault == "plain_altered":
        fold = ws.StreamIngest._fold_plain_decoded

        def altered(self, plain, weight):
            plain = plain.copy()
            plain[0] += 1e-3
            return fold(self, plain, weight)

        monkeypatch.setattr(ws.StreamIngest, "_fold_plain_decoded", altered)
    else:
        monkeypatch.setattr(ws, "_accum_chunks_graph", graph)


def _client_faults(monkeypatch, fault):
    from repro.fl import client as fc
    from repro.wire import stream as ws

    if fault in ("state_unchanged", "half_batch"):
        make = fc.FLClient._make_step

        def broken(self):
            inner = make(self)
            if fault == "state_unchanged":
                return lambda p, s, b, g: (p,) + tuple(inner(p, s, b, g)[1:])
            return lambda p, s, b, g: inner(
                p, s, {k: v[: v.shape[0] // 2] for k, v in b.items()}, g)

        monkeypatch.setattr(fc.FLClient, "_make_step", broken)
        return
    pack = ws.pack_update_frames

    def altered(upd, **kw):
        if fault == "ct_altered":
            sd = kw["seeded"]
            c0 = np.array(sd.c0)
            c0.reshape(-1)[0] ^= np.uint32(1 << 28)
            kw["seeded"] = dataclasses.replace(sd, c0=c0)
        else:
            upd = dataclasses.replace(upd, plain=upd.plain.at[0].add(1e-3))
        return pack(upd, **kw)

    monkeypatch.setattr(ws, "pack_update_frames", altered)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered", "plain_altered",
                                   "samples_misread"])
def test_fold_fault_is_not_correct(root, monkeypatch, fault):
    _fold_faults(monkeypatch, fault)
    r = _run(root, "smoke.fold")
    assert r["correct"] is False and r["failed"] == r["attempted"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "ct_altered", "plain_altered"])
@pytest.mark.parametrize("name", CLIENTS)
def test_client_fault_is_not_correct(root, monkeypatch, name, fault):
    _client_faults(monkeypatch, fault)
    r = _run(root, name)
    assert r["correct"] is False and r["failed"] == r["attempted"]
