"""The Pallas exact scan in interpret mode against the plain top-k.

At the store's width (768-dim rows plus the three flag columns) and a
power-of-two capacity the kernel reads the DB as it lies, with no pad;
a width past the whole-width VMEM budget and a row count off the tile
take the tiled, padded path.  ``kernels.mips_topk.padded_launches``
tells the two apart.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mips_topk import ops as mips_ops
from repro.kernels.mips_topk.kernel import db_padded
from repro.kernels.mips_topk.ref import mips_topk_ref
from repro.obs.metrics import global_registry

FLAGS = (mips_ops.MASK_BIAS, 0.0, 0.0)


@pytest.mark.parametrize("n,d,padded", [
    (64, 768, False), (1024, 768, False), (1000, 2300, True)])
def test_flagged_scan_matches_ref(n, d, padded):
    rng = np.random.default_rng(n + d)
    q = rng.standard_normal((16, d)).astype(np.float32)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    flags = (rng.random((n, len(FLAGS))) < 0.25).astype(np.float32)
    db = np.concatenate([emb, flags], axis=1)
    pads = global_registry().counter("kernels.mips_topk.padded_launches")
    before = pads.count

    vals, idx = mips_ops.flagged_mips_topk(
        jnp.asarray(q), jnp.asarray(db), 8, FLAGS, use_pallas=True,
        interpret=True)

    assert db_padded(n, d + len(FLAGS)) is padded
    assert pads.count - before == int(padded)
    rv, ri = mips_topk_ref(mips_ops.augment_queries(jnp.asarray(q), FLAGS),
                           jnp.asarray(db), 8)
    assert np.array_equal(np.asarray(idx), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv),
                               rtol=1e-5, atol=1e-5)
    # rows flagged dead never outrank a live one
    assert not flags[np.asarray(idx), 0].any()
