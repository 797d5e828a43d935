"""Every Pallas kernel on the TPU path compiles for a described v5e chip,
and a decode step compiled for it writes its cache in place.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached (``v5e:2x2``).  Each kernel is
compiled at the shapes ``chip_smoke.py`` drives it with, and the compiled
HLO must hold the Mosaic kernel (``tpu_custom_call``) — so a tiling or
lowering refusal fails here instead of on the chip.  The topology is
described inside a fixture, never at import, and the persistent
compilation cache is off around the compiles (a described device's
executables cannot be read back).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, reduced_config
from repro.models import transformer as T

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


def _decode_case(kind):
    """Config, batch and cache length of a decode cell per cache kind: four
    scanned groups at the published attention widths (the compiler
    unrolls a loop of two), at a decode cell's batch and length."""
    smollm = dataclasses.replace(get_config("smollm-360m"), n_layers=4,
                                 vocab_size=512)
    if kind == "kv":
        return smollm, 64, 1280
    if kind == "int8":
        return dataclasses.replace(smollm, kv_quant=True), 64, 1280
    if kind == "latent":        # a leading dense layer and four MoE groups
        return dataclasses.replace(
            get_config("deepseek-v2-lite"), n_layers=5, vocab_size=512,
            d_ff=256, moe_d_ff=64, n_experts=8, experts_held=(0, 2)), 32, 2560
    # mamba: a hybrid group of seven Mamba layers and one attention layer
    return dataclasses.replace(reduced_config(get_config("jamba-v0.1-52b")),
                               n_layers=32, n_heads=8, n_kv_heads=4), 64, 2048


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@pytest.mark.parametrize("kind", ["kv", "latent", "int8", "mamba"])
def test_decode_step_copies_no_cache(one_chip, kind):
    """A decode step compiled for the chip, its cache donated, writes its
    slot into the cache it was given: the cache's bytes are aliased to the
    output, and the temporaries do not grow with the cache's length by as
    much as one layer's cache (a layer's cache sliced out of the stack, or
    written back into it, would).  Weights are in the compute dtype, so
    that no per-step weight convert shares the temporaries."""
    cfg, b, length = _decode_case(kind)
    cfg = dataclasses.replace(cfg, remat=False)
    dt = jnp.dtype(cfg.dtype)

    def place(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype or a.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: T.init_lm(jax.random.PRNGKey(0), cfg)), dt)
    token = place(jax.ShapeDtypeStruct((b, 1), jnp.int32))
    pos = place(jax.ShapeDtypeStruct((), jnp.int32))

    def step(params, cache, token, pos):
        logits, cache, _ = T.apply_lm(params, cfg, token, mode="decode",
                                      cache=cache, positions=pos.reshape(1,))
        return jnp.argmax(logits[:, -1], axis=-1), cache

    temp, layer = {}, 0
    for l in (length, 2 * length):
        cache = place(jax.eval_shape(lambda: T.init_cache(cfg, b, l, dt)))
        m = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, token, pos).compile().memory_analysis()
        assert m.alias_size_in_bytes >= _nbytes(cache)
        temp[l] = m.temp_size_in_bytes
        if l == length:
            layer = max(_nbytes(c) for c in cache["groups"].values()) \
                // cfg.n_groups
    assert temp[2 * length] - temp[length] < layer, (temp, layer)
