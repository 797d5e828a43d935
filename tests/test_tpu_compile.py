"""Every Pallas kernel on the TPU path compiles for a described v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached (``v5e:2x2``).  Each kernel is
compiled at the shapes ``chip_smoke.py`` drives it with, and the compiled
HLO must hold the Mosaic kernel (``tpu_custom_call``) — so a tiling or
lowering refusal fails here instead of on the chip.  The topology is
described inside a fixture, never at import, and the persistent
compilation cache is off around the compiles (a described device's
executables cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import kernel as fk
from repro.kernels.hash_partition import kernel as hk
from repro.kernels.segment_reduce import kernel as sk
from repro.kernels.window_scan import kernel as wk

#: rows of the one-chip smoke's event table (2^25)
ROWS = 1 << 25


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("lanes,segments", [(2, 257), (1, 1000)])
def test_segment_sum_compiles(one_chip, lanes, segments):
    """The groupby's fused sum lanes (count + value) at the smoke's rows."""
    _compile(lambda v, s: sk.segment_reduce_pallas(v, s, segments, "sum"),
             one_chip, ((lanes, ROWS), jnp.float32), ((ROWS,), jnp.int32))


@pytest.mark.parametrize("op", ["min", "max"])
def test_segment_minmax_compiles(one_chip, op):
    _compile(lambda v, s: sk.segment_reduce_pallas(v, s, 257, op),
             one_chip, ((ROWS,), jnp.float32), ((ROWS,), jnp.int32))


@pytest.mark.parametrize("window,lanes,op", [
    (7, 2, "sum"), (7, 1, "max"), (1, 1, "sum"), (600, 3, "min")])
def test_window_scan_compiles(one_chip, window, lanes, op):
    """The rolling window (7 rows, sum+count lanes) and wider halos."""
    _compile(lambda v, s: wk.windowed_scan_pallas(v, s, window, op),
             one_chip, ((ROWS, lanes), jnp.float32), ((ROWS,), jnp.int32))


@pytest.mark.parametrize("seq", [512, 2048])
def test_flash_attention_compiles(one_chip, seq):
    """smollm-360m prefill: 15 query heads over 5 KV heads, head dim 64."""
    _compile(lambda q, k, v: fk.flash_attention_pallas(q, k, v, causal=True),
             one_chip, ((4, 15, seq, 64), jnp.bfloat16),
             ((4, 5, seq, 64), jnp.bfloat16), ((4, 5, seq, 64), jnp.bfloat16))


@pytest.mark.parametrize("n_cols", [1, 2])
def test_hash_partition_compiles(one_chip, n_cols):
    """A four-shard exchange's destination + hash pass over one shard."""
    _compile(lambda k, v: hk.hash_partition_pallas(k, v, 4,
                                                   return_hashes=True),
             one_chip, ((n_cols, ROWS), jnp.uint32), ((ROWS,), jnp.int32))
