"""Compile the main-path kernels for a described TPU v5e (no chip).

The TPU compiler refuses what interpret mode accepts: an unsigned
reduction Mosaic cannot lower, a top-k merge that overflows VMEM at
large query tiles.  These compiles run the real compiler at the widths
the store serves (768-dim rows plus 3 flag columns), about a second
each.  The topology is described inside a fixture, never at import: a
process that describes it holds the TPU library until it exits.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, \
    PartitionSpec as P, SingleDeviceSharding

from repro.kernels.hamming_topk.kernel import hamming_topk_pallas
from repro.kernels.lsh_hash.kernel import lsh_hash_pallas
from repro.kernels.mips_topk import ops as mips_ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent
    # cache but can never be read back here: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _buffer_sized_ops(text: str, n: int) -> list:
    """HLO ops, other than parameters and bitcasts, with ``n`` in their
    result shape: a copy, pad or relayout of an ``(n, d)`` DB."""
    shape = re.compile(rf"= f32\[[0-9,]*\b{n}\b[0-9,]*\]\S* (\w[\w-]*)\(")
    return [m.group(0) for m in shape.finditer(text)
            if m.group(1) not in ("parameter", "bitcast")]


# the store's buffer, (cap, 768 + 3 flags), is column-major on the chip:
# the scan reads it in place, with no pad, copy or temp buffer
@pytest.mark.parametrize("b,n", [
    pytest.param(8, 65_536, id="8"), pytest.param(64, 65_536, id="64"),
    pytest.param(128, 65_536, id="128"), (1, 131_072), (16, 131_072),
    (64, 131_072)])
def test_mips_topk_compiles(one_chip, b, n):
    q = jax.ShapeDtypeStruct((b, 771), jnp.float32, sharding=one_chip)
    db = jax.ShapeDtypeStruct((n, 771), jnp.float32, sharding=one_chip)
    compiled = mips_ops._mips_topk.lower(
        q, db, 8, use_pallas=True, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _buffer_sized_ops(text, n) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("n,d", [(4096, 768), (13, 256)])
def test_lsh_hash_compiles(one_chip, n, d):
    v = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    h = jax.ShapeDtypeStruct((d, 12), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(lsh_hash_pallas, v, h)


def test_hamming_topk_compiles(one_chip):
    qc = jax.ShapeDtypeStruct((64, 2), jnp.uint32, sharding=one_chip)
    dbc = jax.ShapeDtypeStruct((1 << 20, 2), jnp.uint32,
                               sharding=one_chip)
    text = _compiled_text(lambda x, y: hamming_topk_pallas(x, y, 32),
                          qc, dbc)
    assert "tpu_custom_call" in text


def test_sharded_mips_topk_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",),
                axis_types=(AxisType.Auto,))
    rep = NamedSharding(mesh, P(None, None))
    q = jax.ShapeDtypeStruct((64, 768), jnp.float32, sharding=rep)
    db = jax.ShapeDtypeStruct((4, 16_384, 771), jnp.float32,
                              sharding=NamedSharding(
                                  mesh, P("data", None, None)))
    seq = jax.ShapeDtypeStruct((4, 16_384), jnp.int32,
                               sharding=NamedSharding(mesh,
                                                      P("data", None)))
    compiled = mips_ops._sharded_mips_topk.lower(
        q, db, seq, k_shard=8, k_out=8,
        flag_bias=(mips_ops.MASK_BIAS, 0.0, 0.0), mesh=mesh,
        axis_names=("data",), use_pallas=True, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
    # each local slot is scanned in place: no slot-sized copy or pad
    assert compiled.memory_analysis().temp_size_in_bytes < 16_384 * 771 * 4
