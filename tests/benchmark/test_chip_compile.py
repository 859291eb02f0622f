"""The cells' step programs, at the published widths and the cells' own
batch and slots, compile for a described TPU v5e and fit its memory — no
chip attached, nothing run. One file, one module-scoped fixture: only the
worker that is given this file loads the TPU's compiler."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import tiny

HBM_BYTES = 15.75 * 2 ** 30        # what a v5e chip lets a program use


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_kernels():
    """The program picks its attention path by the default backend (the CPU
    here); steer it, in the test, to the compiled Pallas kernels the chip
    takes, in 32-bit mode as on the chip, and keep these compiles out of
    the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    from deeplearning4j_tpu.kernels.pallas_attention import \
        register_pallas_flash_attention
    from deeplearning4j_tpu.nn import helpers
    snap = helpers.snapshot_helper("attention")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    register_pallas_flash_attention(platforms=("tpu", "cpu"),
                                    interpret=False)
    # the test session runs in 64-bit mode (conftest.py); the chip does not,
    # and Mosaic has no float64
    with jax.enable_x64(False):
        yield
    helpers.restore_helper("attention", snap)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _config(name):
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(tiny.ROOT, "benchmark", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def _on(tree, sharding, dtype=None):
    def one(a):
        dt = dtype if dtype is not None and a.dtype == jnp.float32 \
            else a.dtype
        return jax.ShapeDtypeStruct(a.shape, dt, sharding=sharding)
    return jax.tree_util.tree_map(one, tree)


def _fits(compiled):
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.output_size_in_bytes \
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB does not fit"
    return total


def test_gpt2_medium_train_step_compiles_and_fits(one_chip,
                                                   compiled_kernels):
    config, traffic = _config("gpt2-medium"), _traffic("train-t1024")
    net, _, (params, state, upd) = tiny.family(config).make_net(config)
    rows, seq = traffic["batch_rows"], traffic["seq_len"]
    ids = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one_chip)
    lowered = net._get_train_step(False).lower(
        _on(params, one_chip), _on(upd, one_chip), _on(state, one_chip),
        {"tokens": ids}, {"out": ids}, None, None, 0, {})
    assert "tpu_custom_call" in lowered.as_text()     # the flash kernels
    total = _fits(lowered.compile())
    held = config["run"]["held_on_device_bytes"]
    assert total > held["weights_gradients_adam_m_v_float32"]


@pytest.fixture(scope="module")
def large_decoder(one_chip):
    from deeplearning4j_tpu.models import TransformerDecoder
    config = _config("gpt2-large")
    net, _, (params, state, _) = tiny.family(config).make_net(config)
    params = _on(params, one_chip, jnp.bfloat16)   # served in bfloat16
    net.params = params
    eng = config["run"]["engine"]
    dec = TransformerDecoder(net, t_max=eng["t_max"])
    # the cache as the engine allocates it (since PR 27 two heads of 64 to
    # a 128-lane row), so that what compiles here is what the cell runs
    caches = _on(jax.eval_shape(lambda: dec.init_cache(eng["num_slots"])),
                 one_chip)
    return dec, params, _on(state, one_chip), caches, eng


def _vec(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def test_gpt2_large_prefill_at_1024_compiles_and_fits(one_chip, large_decoder,
                                                      compiled_kernels):
    dec, params, state, caches, eng = large_decoder
    m, tp = eng["num_slots"], eng["t_max"]
    dec._fn("prefill_slots")
    jitted = dec._cost_seam["prefill_slots_impl"][0]
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    lowered = jitted.lower(
        params, state, caches,
        jax.ShapeDtypeStruct((m, tp), jnp.int32, sharding=one_chip),
        _vec(m, jnp.int32, one_chip), _vec(m, jnp.int32, one_chip),
        _vec(m, jnp.float32, one_chip),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip))
    assert "tpu_custom_call" in lowered.as_text()
    _fits(lowered.compile())


def test_gpt2_large_decode_block_compiles_and_fits(one_chip, large_decoder,
                                                   compiled_kernels):
    dec, params, state, caches, eng = large_decoder
    s, k = eng["num_slots"], eng["block_size"]
    assert caches[dec.attn_names[0]]["k"].shape == (s, 10, 1024, 128)
    dec._fn(("block", k))
    jitted = dec._cost_seam[f"decode_block{k}_impl"][0]
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    lowered = jitted.lower(
        params, state, caches, _vec(s, jnp.int32, one_chip),
        _vec(s, jnp.int32, one_chip), _vec(s, jnp.bool_, one_chip),
        _vec(s, jnp.float32, one_chip), _vec(s, jnp.int32, one_chip),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        scalar, scalar)
    total = _fits(lowered.compile())
    held = _config("gpt2-large")["run"]["held_on_device_bytes"]
    assert total > held["weights_bfloat16"] \
        + held["slab_cache_16_slots_x_1024"]
    # the packed slab is read in place: no padded copies of it (12.1 GB with
    # a [slots, heads, t_max, 64] cache; the file's figure is PR 27's)
    assert total == pytest.approx(
        held["decode_block4_16_slots_peak_memory_analysis"], rel=0.1)
