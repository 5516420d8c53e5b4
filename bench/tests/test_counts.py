"""Operations and bytes against hand-worked shapes."""
from __future__ import annotations

import json
import os

import pytest

import smoke
import counts
import peaks


def test_ciphertext_and_fold_bytes_at_n8192_l2():
    # 2 polynomials x 2 limbs x 8192 words x 4 B = 128 KiB
    assert counts.ciphertext_bytes(8192, 2) == 131072
    # read the ciphertext, read and write its accumulator chunk
    assert counts.fold_bytes(1, 8192, 2) == 3 * 131072
    # one p=0.01 qwen1.5-0.5b update: 1,133 ciphertexts
    assert counts.fold_bytes(1133, 8192, 2) == 445_513_728


def test_dense_train_flops_at_published_qwen_size():
    with open(os.path.join(smoke.BENCH, "configs",
                           "qwen1.5-0.5b-silo.json")) as f:
        c = json.load(f)
    params = c["param_count"]
    assert params == 463_987_712
    tokens = 2 * 4 * 512
    want = 6 * 463_987_712 * 4096 + 6 * 24 * 512 * 1024 * 4096
    assert counts.dense_train_flops(params, tokens, 24, 1024, 512) == want
    # ~11.7 TFLOP for one update's two steps: 59 ms at the v5e's bf16 peak
    assert want / peaks.peaks("TPU v5 lite").bf16_flops == pytest.approx(
        0.0595, abs=1e-3)


def test_program_param_count_matches_the_published_one():
    import spec
    from repro.models import build_model
    from repro.core import packing

    with open(os.path.join(smoke.BENCH, "configs",
                           "qwen1.5-0.5b-silo.json")) as f:
        c = json.load(f)
    m = build_model(spec.model_config(c, "qwen1.5-0.5b-silo"))
    assert packing.make_flat_spec(m.init_abstract()).total == \
        c["param_count"]


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError, match="no published peak"):
        peaks.peaks("TPU v99")

