"""Property tests for the mask selectors (core/selection.py) and fuzz for
the mask partition (core/packing.py) — the static halves of the selective
pipeline.

Invariants pinned here:
  * masks NEST across p for top_p / random / per_layer (fixed sensitivity,
    fixed seed): mask(p1) subset mask(p2) whenever p1 <= p2
  * recipe_mask always fully covers the first and last leaves
  * ties on |sensitivity| break deterministically by index (lowest wins)
  * make_partition / split_by_mask / merge_by_mask round-trip any mask —
    empty, full, non-slot-aligned, ragged last chunk
  * the host split gives the bits of the device gathers it replaced
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hyp import given, settings, st

from repro.core import packing, selection

NESTING_STRATEGIES = ["top_p", "random", "per_layer"]


def _layout(n, n_leaves=3):
    """An arbitrary leaf layout covering [0, n) for layer-aware selectors."""
    cuts = np.linspace(0, n, n_leaves + 1).astype(int)
    sizes = tuple(int(b - a) for a, b in zip(cuts[:-1], cuts[1:])
                  if b - a > 0)
    offsets = tuple(int(x) for x in np.concatenate(
        [[0], np.cumsum(sizes)[:-1]])) if sizes else ()
    return offsets, sizes


# ---------------------------------------------------------------------------
# nesting across p (hypothesis + a deterministic pinned case)
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.lists(st.floats(-1e6, 1e6, allow_nan=False), min_size=1,
                max_size=200),
       st.floats(0.0, 1.0), st.floats(0.0, 1.0),
       st.sampled_from(NESTING_STRATEGIES), st.integers(0, 2 ** 31 - 1))
def test_masks_nest_across_p(sens, p1, p2, strategy, seed):
    sens = np.asarray(sens)
    lo, hi = sorted((p1, p2))
    offsets, sizes = _layout(sens.size)
    m_lo = selection.build_mask(sens, strategy, lo, offsets=offsets,
                                sizes=sizes, seed=seed)
    m_hi = selection.build_mask(sens, strategy, hi, offsets=offsets,
                                sizes=sizes, seed=seed)
    assert not np.any(m_lo & ~m_hi), "smaller-p mask escaped the larger one"


@pytest.mark.parametrize("strategy", NESTING_STRATEGIES)
def test_masks_nest_across_sweep(strategy):
    rng = np.random.RandomState(0)
    sens = rng.randn(997)
    offsets, sizes = _layout(sens.size, n_leaves=5)
    prev = None
    for p in (0.0, 0.05, 0.1, 0.3, 0.5, 1.0):
        m = selection.build_mask(sens, strategy, p, offsets=offsets,
                                 sizes=sizes, seed=3)
        if prev is not None:
            assert not np.any(prev & ~m)
        prev = m
    assert prev.all()                              # p=1.0 covers everything


# ---------------------------------------------------------------------------
# recipe covers first + last leaves
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.lists(st.floats(-1e6, 1e6, allow_nan=False), min_size=2,
                max_size=200),
       st.floats(0.0, 1.0), st.integers(1, 6))
def test_recipe_covers_first_and_last_leaves(sens, p, n_leaves):
    sens = np.asarray(sens)
    offsets, sizes = _layout(sens.size, n_leaves=n_leaves)
    m = selection.build_mask(sens, "recipe", p, offsets=offsets, sizes=sizes)
    assert m[offsets[0]: offsets[0] + sizes[0]].all()
    assert m[offsets[-1]: offsets[-1] + sizes[-1]].all()
    # and it is a superset of plain top_p at the same p
    assert not np.any(selection.top_p_mask(sens, p) & ~m)


# ---------------------------------------------------------------------------
# deterministic tie-break
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 300), st.floats(0.0, 1.0))
def test_top_p_tie_break_is_by_index(n, p):
    sens = np.full(n, 2.5)                         # all-equal sensitivities
    m = selection.top_p_mask(sens, p)
    k = int(m.sum())
    # lowest indices win — the mask is exactly a prefix
    assert m[:k].all() and not m[k:].any()


def test_tie_break_stable_under_sign_and_dtype():
    sens = np.asarray([1.0, -1.0, 1.0, -1.0, 0.5], dtype=np.float32)
    m = selection.top_p_mask(sens, 0.4)            # k=2: |1.0| ties, idx wins
    np.testing.assert_array_equal(m, [True, True, False, False, False])
    m64 = selection.top_p_mask(sens.astype(np.float64), 0.4)
    np.testing.assert_array_equal(m, m64)


def test_build_mask_dispatch_errors():
    with pytest.raises(ValueError, match="unknown selection strategy"):
        selection.build_mask(np.ones(4), "bogus", 0.5)
    with pytest.raises(ValueError, match="leaf layout"):
        selection.build_mask(np.ones(4), "recipe", 0.5)
    assert selection.build_mask(np.ones(4), "all", 0.0).all()
    assert not selection.build_mask(np.ones(4), "none", 1.0).any()


# ---------------------------------------------------------------------------
# partition fuzz: adversarial masks round-trip split/merge
# ---------------------------------------------------------------------------

SLOTS = 8


def _roundtrip(mask, slots=SLOTS):
    mask = np.asarray(mask, dtype=bool)
    part = packing.make_partition(mask, slots)
    # invariants: enc/plain indices disjointly cover [0, n)
    assert part.n_enc + part.n_plain == part.n_total == mask.size
    both = np.concatenate([part.enc_idx, part.plain_idx])
    assert np.array_equal(np.sort(both), np.arange(mask.size))
    assert part.n_chunks == max(1, -(-part.n_enc // slots))
    vec = jnp.asarray(
        np.random.RandomState(mask.size).randn(mask.size).astype(np.float32))
    enc, plain = packing.split_by_mask(vec, part)
    assert enc.shape == (part.n_chunks, slots)     # zero-padded ragged tail
    assert int(plain.shape[0]) == part.n_plain
    back = packing.merge_by_mask(enc, plain, part)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(vec))
    return part


@pytest.mark.parametrize("mask", [
    np.zeros(37, dtype=bool),                      # empty -> 1 all-pad chunk
    np.ones(37, dtype=bool),                       # full, non-slot-aligned
    np.ones(SLOTS * 3, dtype=bool),                # full, slot-aligned
    np.arange(61) % 2 == 0,                        # interleaved, ragged
    np.arange(9) < 8,                              # exactly one full chunk
    np.zeros(1, dtype=bool),                       # single param, plain
    np.ones(1, dtype=bool),                        # single param, encrypted
])
def test_partition_roundtrip_adversarial(mask):
    part = _roundtrip(mask)
    if not mask.any():
        assert part.n_chunks == 1                  # never a 0-chunk ct


@settings(max_examples=60, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=120),
       st.integers(1, 16))
def test_partition_roundtrip_fuzz(bits, slots):
    _roundtrip(np.asarray(bits, dtype=bool), slots=slots)


# ---------------------------------------------------------------------------
# host split against the device gathers, bit for bit
# ---------------------------------------------------------------------------

SPLIT_N, SPLIT_SLOTS = 4096, 16


def _gather_split(vec, part):
    """The split as the device did it: jnp gathers of both index arrays."""
    enc = vec[jnp.asarray(part.enc_idx)]
    enc = jnp.pad(enc, (0, part.n_enc_padded - part.n_enc))
    return (enc.reshape(part.n_chunks, part.slots),
            vec[jnp.asarray(part.plain_idx)])


def _split_mask(kind):
    rng = np.random.RandomState(7)
    if kind == "empty":
        return np.zeros(SPLIT_N, dtype=bool)
    if kind == "full":
        return np.ones(SPLIT_N, dtype=bool)
    if kind == "ragged":                 # 41 = 2 whole chunks + 9
        mask = np.zeros(SPLIT_N, dtype=bool)
        mask[rng.choice(SPLIT_N, 41, replace=False)] = True
        return mask
    if kind == "slot_blocks":            # exactly 3 whole chunks
        mask = np.zeros(SPLIT_N, dtype=bool)
        mask[rng.choice(SPLIT_N, 3 * SPLIT_SLOTS, replace=False)] = True
        return mask
    if kind == "edges":                  # the vector's first and last value
        mask = np.zeros(SPLIT_N, dtype=bool)
        mask[[0, SPLIT_N - 1]] = True
        return mask
    if kind == "recipe_leaves":          # top 1% plus whole first/last leaf
        offsets, sizes = _layout(SPLIT_N, n_leaves=5)
        return selection.build_mask(rng.rand(SPLIT_N), "recipe", 0.01,
                                    offsets=offsets, sizes=sizes)
    return selection.random_mask(0.01, SPLIT_N, seed=7)   # random_1pct


def _bits(x):
    x = np.asarray(x)
    return x.dtype, x.shape, x.view(np.uint32)


@pytest.mark.parametrize("kind", ["empty", "full", "random_1pct", "ragged",
                                  "slot_blocks", "edges", "recipe_leaves"])
def test_host_split_matches_device_gather_bits(kind):
    part = packing.make_partition(_split_mask(kind), SPLIT_SLOTS)
    host = np.random.RandomState(3).randn(SPLIT_N).astype(np.float32)
    host[:4] = [-0.0, np.inf, -np.inf, 1e-40]       # bits a copy keeps
    vec = jnp.asarray(host)
    got = packing.split_by_mask(vec, part)
    assert all(type(x) is np.ndarray for x in got)   # stays on the host
    want = _gather_split(vec, part)
    for g, w in zip(got, want):
        gd, gs, gb = _bits(g)
        wd, ws, wb = _bits(w)
        assert gd == wd == np.float32 and gs == ws
        np.testing.assert_array_equal(gb, wb)
    assert got[0].shape == (part.n_chunks, SPLIT_SLOTS)
    if kind == "ragged":
        assert part.n_enc % SPLIT_SLOTS
    if kind == "slot_blocks":
        assert part.n_enc == part.n_enc_padded
    if kind == "edges":
        assert got[0][0, :2].tolist() == [host[0], host[-1]]
    if kind == "recipe_leaves":
        offsets, sizes = _layout(SPLIT_N, n_leaves=5)
        assert part.enc_idx[0] == 0 and part.n_enc > sizes[0] + sizes[-1]


@pytest.mark.parametrize("codec,dp_b", [("f32", 0.0), ("i8", 0.0),
                                        ("f32", 0.05)])
def test_seeded_protect_and_pack_host_split_bytes(monkeypatch, codec, dp_b):
    """The host split packs the blob the device gathers packed, byte for
    byte, DP noise under a fixed key included, and leaves the partition's
    wire bytes as they were."""
    from repro.core.ckks import cipher, params as ckks_params
    from repro.core.secure_agg import AggregatorConfig, SelectiveHEAggregator
    from repro.fl.client import FLClient
    from repro.wire import compress as wc, format as wf

    class _NoModel:
        loss_fn = staticmethod(lambda params, batch: 0.0)

    ctx = ckks_params.make_test_context(n_poly=256, n_limbs=2, delta_bits=20)
    sk, _ = cipher.keygen(ctx, jax.random.PRNGKey(0))
    r = np.random.RandomState(1)
    m = {"w1": jnp.asarray(r.randn(40, 10), jnp.float32),
         "b1": jnp.asarray(r.randn(50), jnp.float32)}
    agg = SelectiveHEAggregator.build(
        ctx, m, np.abs(r.randn(450)), AggregatorConfig(p_ratio=0.4, dp_b=dp_b))
    part_bytes = wf.serialize_partition(agg.part)
    cli = FLClient(2, _NoModel(), None)
    policy = wc.WirePolicy(seed_ciphertexts=True, plain_codec=codec)

    def pack():
        return cli.protect_and_pack(agg, m, rnd=1, policy=policy, sk=sk,
                                    key=jax.random.PRNGKey(77),
                                    mode="seeded")

    host = pack()
    assert wf.serialize_partition(agg.part) == part_bytes
    monkeypatch.setattr(packing, "split_by_mask", _gather_split)
    assert host == pack()
