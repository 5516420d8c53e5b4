"""Quickstart: encrypt a model update, aggregate under CKKS, decrypt —
then ship the same round over the repro.wire serialized transport and
print the measured per-round bandwidth ledger.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import packing, selection
from repro.core.ckks import cipher, params as ckks_params
from repro.core.secure_agg import AggregatorConfig, SelectiveHEAggregator
from repro import wire
from repro.wire import budget as wb
from repro.wire import stream as ws
from repro.launch import compile_cache


def main():
    compile_cache.enable()
    # 1. crypto context (paper defaults scaled down for a quick run:
    #    packing batch 512 slots, depth-1, two ~29-bit RNS limbs)
    ctx = ckks_params.make_context(n_poly=1024, n_limbs=2, delta_bits=24)
    sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
    print(f"CKKS: N={ctx.n_poly} slots={ctx.slots} logQ~{ctx.log_q:.0f} "
          f"delta=2^{ctx.delta_bits}")

    # 2. a 'model' + per-parameter sensitivity (here synthetic; see
    #    examples/encrypted_finetune.py for real sensitivity maps)
    rng = np.random.RandomState(0)
    model = {"w1": jnp.asarray(rng.randn(256, 64), jnp.float32),
             "w2": jnp.asarray(rng.randn(64, 10), jnp.float32)}
    n_params = 256 * 64 + 64 * 10
    sens = np.abs(rng.randn(n_params))

    # 3. Selective Parameter Encryption at p=0.1
    agg = SelectiveHEAggregator.build(
        ctx, model, sens, AggregatorConfig(p_ratio=0.1, strategy="top_p"))
    rep = agg.overhead_report()
    print(f"encrypting {rep['n_enc']}/{rep['n_total']} params "
          f"({rep['ratio']:.0%}) in {rep['n_ciphertexts']} ciphertexts; "
          f"comm ratio vs plaintext {rep['comm_ratio']:.2f}x")

    # 4. three clients -> encrypted FedAvg -> decrypt
    clients = [jax.tree_util.tree_map(lambda x: x + 0.1 * i, model)
               for i in range(3)]
    updates = [agg.client_protect(m, pk, jax.random.PRNGKey(10 + i))
               for i, m in enumerate(clients)]
    glob = agg.server_aggregate(updates, [1 / 3] * 3)
    recovered = agg.client_recover_params(glob, sk)

    expect = jax.tree_util.tree_map(lambda *xs: sum(xs) / 3, *clients)
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(recovered),
        jax.tree_util.tree_leaves(expect)))
    print(f"aggregation max error vs plaintext FedAvg: {err:.2e}")
    assert err < 1e-2

    # 5. same round over the wire: seed-expanded uplink ciphertexts, fp16
    #    plaintext partition, streaming server ingest, measured bytes —
    #    traced as one "round" span tree when REPRO_OBS=1
    ledger = wb.BandwidthLedger()
    with obs.span("round", round=0) as rsp:
        blobs = []
        for i, m in enumerate(clients):
            with obs.span("encrypt", cid=i) as esp:
                upd = agg.client_protect_seeded(
                    m, sk, jax.random.PRNGKey(20 + i), a_seed=100 + i)
                sct = wire.seed_compress(upd.ct, 100 + i)
                blob = ws.pack_update_frames(upd, cid=i, n_samples=4,
                                             rnd=0, seeded=sct,
                                             plain_codec="f16")
                esp.set(nbytes=len(blob))
            ledger.record_blob(blob, rnd=0, cid=i, direction=wb.UPLINK)
            blobs.append(blob)
        with obs.span("aggregate", n_updates=len(blobs)):
            ingest = ws.StreamIngest(ctx)
            for blob in blobs:
                ingest.ingest(blob, 1 / 3)
            glob_wire = ingest.finalize()
        with obs.span("broadcast", n_clients=len(clients)):
            blob_down = wire.serialize_update(glob_wire)
            for i in range(len(clients)):
                ledger.record_blob(blob_down, rnd=0, cid=i,
                                   direction=wb.DOWNLINK)
        with obs.span("recover"):
            rec_wire = obs.maybe_block(
                agg.client_recover_params(glob_wire, sk))
        rsp.set(bytes_up=ledger.total(wb.UPLINK, 0),
                bytes_down=ledger.total(wb.DOWNLINK, 0),
                launches=ingest.accum_launches)
    err_w = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(rec_wire),
        jax.tree_util.tree_leaves(expect)))
    assert err_w < 1e-2, err_w
    # O(1)-in-clients server memory: at most one update's ready chunks
    # resident, folded by ONE accumulate launch per client update
    assert ingest.peak_chunk_buffers == agg.part.n_chunks
    assert ingest.accum_launches == ingest.clients_ingested

    s = ledger.round_summary(0)
    comp = ledger.compression_summary(ctx, agg.part, 0)
    print("\nper-round bandwidth ledger (measured bytes on the wire):")
    print(f"  uplink   {s['uplink_bytes']:>9,} B total "
          f"({comp['uplink_bytes_per_client']:,} B/client)")
    print(f"  downlink {s['downlink_bytes']:>9,} B total")
    for kind, nbytes in sorted(s["by_kind"].items()):
        print(f"    {kind:<24} {nbytes:>9,} B")
    print(f"  compression vs naive all-encrypted uplink: "
          f"{comp['compression_ratio']:.1f}x "
          f"({comp['naive_all_encrypted_bytes']:,} B -> "
          f"{comp['measured_uplink_bytes']:,} B)")
    if obs.enabled():
        obs.flush()
        print(f"\ntrace written to {obs.trace_path()} "
              f"(open in Perfetto, or: "
              f"python tools/round_report.py {obs.trace_path()})")
    print("OK")


if __name__ == "__main__":
    main()
