"""Operations and bytes of the measured work, from shapes alone.

Every roofline and mfu share divides one of these by a time from the
device trace or the window and by a peak from peaks.py.
"""
from __future__ import annotations

U32 = 4


def ciphertext_bytes(n_poly: int, n_limbs: int) -> int:
    """One RNS-CKKS ciphertext: 2 polynomials x L limbs x N u32 words."""
    return 2 * n_limbs * n_poly * U32


def fold_bytes(n_ct: int, n_poly: int, n_limbs: int) -> int:
    """Bytes the weighted accumulate must move to fold n_ct ciphertexts:
    each ciphertext read once, its accumulator chunk read and written."""
    return 3 * n_ct * ciphertext_bytes(n_poly, n_limbs)


def dense_train_flops(params: int, tokens: int, n_layers: int, d_model: int,
                      seq_len: int) -> float:
    """Model FLOPs of forward and backward over `tokens` tokens of a dense
    causal decoder: 6 per parameter per token (every weight, the tied
    unembedding included, is one multiply-add forward and two backward),
    plus causal attention, 6 * n_layers * seq_len * d_model per token
    (scores and values, half the square, times three for the backward).
    Recomputation is not counted."""
    return 6.0 * params * tokens + 6.0 * n_layers * seq_len * d_model * tokens

