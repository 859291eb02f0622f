"""``granite-4.0-h-micro``'s step programs, at the published widths and the
cell's own 32 slots, compile for a described TPU v5e and fit its memory — no
chip attached, nothing run: the decode block (36 state-update kernels and 4
grouped-head slab kernels in it) and the largest admission program the
warm-up sends (32 x the traffic's longest prompt). One module-scoped
fixture, as in ``test_chip_compile.py``: only the worker that is given this
file loads the TPU's compiler, and where no topology can be described the
tests skip."""

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
    """The program picks its kernels by the default backend (the CPU here);
    steer it, in the test, to the compiled Pallas kernels the chip takes —
    flash attention, the slab's streaming kernel and the state update — in
    32-bit mode as on the chip, and keep these compiles out of the
    persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    from deeplearning4j_tpu.kernels.pallas_attention import \
        register_pallas_flash_attention
    from deeplearning4j_tpu.kernels.slab_attention import \
        register_slab_attention
    from deeplearning4j_tpu.kernels.ssm_update import register_ssm_update
    from deeplearning4j_tpu.nn import helpers
    kinds = ("attention", "slab_attention", "ssm_update")
    snaps = {k: helpers.snapshot_helper(k) for k in kinds}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    register_pallas_flash_attention(platforms=("tpu", "cpu"),
                                    interpret=False)
    register_slab_attention(platforms=("tpu", "cpu"), interpret=False)
    register_ssm_update(platforms=("tpu", "cpu"), interpret=False)
    with jax.enable_x64(False):
        yield
    for kind, snap in snaps.items():
        helpers.restore_helper(kind, snap)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(tree, sharding, dtype=None):
    def one(a):
        dt = dtype if dtype is not None and a.dtype == jnp.float32 \
            else a.dtype
        return jax.ShapeDtypeStruct(a.shape, dt, sharding=sharding)
    return jax.tree_util.tree_map(one, tree)


def _vec(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _peak(compiled):
    ma = compiled.memory_analysis()
    return ma.argument_size_in_bytes + ma.output_size_in_bytes \
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes


@pytest.fixture(scope="module")
def served(one_chip):
    from deeplearning4j_tpu.models import TransformerDecoder
    bench = os.path.join(tiny.ROOT, "benchmark")
    with open(os.path.join(bench, "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "chat-short.json")) as f:
        longest = json.load(f)["prompt_tokens"]["max"]
    net, _, (params, state, _) = tiny.family(config).make_net(config)
    params = _on(params, one_chip, jnp.bfloat16)   # served in bfloat16
    net.params = params
    eng = config["run"]["engine"]
    dec = TransformerDecoder(net, t_max=eng["t_max"])
    caches = _on(jax.eval_shape(lambda: dec.init_cache(eng["num_slots"])),
                 one_chip)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    return dec, params, _on(state, one_chip), caches, eng, key, \
        config["run"]["held_on_device_bytes"], longest


def test_decode_block_compiles_fits_and_calls_both_kernels(
        one_chip, served, compiled_kernels):
    dec, params, state, caches, eng, key, held, _ = served
    s, k = eng["num_slots"], eng["block_size"]
    assert len(dec.state_names) == 36 and len(dec.kv_names) == 4
    assert caches["ssm0"]["ssm"].shape == (s, 64, 64, 128)
    assert caches["ssm0"]["conv"].shape == (s, 3, 4352)
    assert caches["attn5"]["k"].shape == (s, 4, eng["t_max"], 128)
    dec._fn(("block", k))
    jitted = dec._cost_seam[f"decode_block{k}_impl"][0]
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jitted.lower(
        params, state, caches, _vec(s, jnp.int32, one_chip),
        _vec(s, jnp.int32, one_chip), _vec(s, jnp.bool_, one_chip),
        _vec(s, jnp.float32, one_chip), _vec(s, jnp.int32, one_chip), key,
        scalar, scalar).compile()
    text = compiled.as_text()
    assert "ssm_decode_update" in text and "slab_decode_attn" in text
    total = _peak(compiled)
    print(f"decode_block{k}_impl peak {total}")
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB does not fit"
    assert total > held["weights_bfloat16"] + held["ssm_state_32_slots"]
    assert total == pytest.approx(
        held["decode_block4_32_slots_peak_memory_analysis"], rel=0.05)


def test_largest_admission_program_compiles_and_fits(one_chip, served,
                                                     compiled_kernels):
    dec, params, state, caches, eng, key, held, longest = served
    m, tp = eng["num_slots"], longest
    dec._fn("prefill_slots")
    jitted = dec._cost_seam["prefill_slots_impl"][0]
    lowered = jitted.lower(
        params, state, caches,
        jax.ShapeDtypeStruct((m, tp), jnp.int32, sharding=one_chip),
        _vec(m, jnp.int32, one_chip), _vec(m, jnp.int32, one_chip),
        _vec(m, jnp.float32, one_chip), key)
    total = _peak(lowered.compile())
    print(f"prefill_slots_impl {m} x {tp} peak {total}")
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB does not fit"
    assert total == pytest.approx(
        held[f"prefill_slots_{m}x{tp}_peak_memory_analysis"], rel=0.05)
