"""The reader of the client's mask split: seconds per update of the
program's `protect.split` span while a profiler session records, and None
where the split did not run, where the run was not traced, or where no
update completed."""
from __future__ import annotations

import types

import numpy as np
import pytest

import smoke  # noqa: F401  (puts bench/ and src/ on the path)
import spec


def _run(updates, traced=True):
    return types.SimpleNamespace(work={"updates": updates},
                                 trace=object() if traced else None)


def test_split_reader_takes_profiled_splits_per_update(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core import packing

    part = packing.make_partition(np.arange(100) % 7 == 0, 8)
    vec = jnp.arange(100, dtype=jnp.float32)
    packing.split_by_mask(vec, part)   # outside any profiler session
    reader = spec.reader("split_s.client")
    h = obs.REGISTRY.get("obs_span_seconds", span="protect.split")
    before = 0 if h is None else h.count
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            packing.split_by_mask(vec, part)
    h = obs.REGISTRY.get("obs_span_seconds", span="protect.split")
    assert h.count - before == 3
    assert reader(_run(3)) == pytest.approx(h.sum / 3)
    # untraced, or no update: nothing to read
    assert reader(_run(3, traced=False)) is None
    assert reader(_run(0)) is None
