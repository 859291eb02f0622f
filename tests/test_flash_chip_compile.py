"""One packed attention layer, forward and backward, at the training cell's
shape (B 8, T 1024, H 16, Dh 64, bfloat16, causal), compiled with the real
kernels for a described TPU v5e — no chip attached, nothing run (what
tests/benchmark/test_chip_compile.py does for the cells' whole programs).
The three Mosaic calls lower, and the compiled program moves no q-sized
array through a transpose or a layout copy on its way into or out of a
``flash_*`` call. Beside it one layer's ``decode_forward`` over the slab at
the serving cell's shape, the streaming decode kernel forced. One file,
module-scoped fixtures: only the worker that is given this file loads the
TPU's compiler."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

B, T, H, DH = 8, 1024, 16, 64


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
def compiled_layer(one_chip):
    """(lowered text, compiled text, plans taken) of value-and-gradient of
    one SelfAttentionLayer through the compiled Pallas kernels, in 32-bit
    mode as on the chip, kept out of the persistent compile cache."""
    from jax.experimental.compilation_cache import compilation_cache
    from deeplearning4j_tpu.analysis import AttentionPlanAudit
    from deeplearning4j_tpu.kernels.pallas_attention import \
        register_pallas_flash_attention
    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    snap = helpers.snapshot_helper("attention")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    register_pallas_flash_attention(platforms=("tpu", "cpu"),
                                    interpret=False)
    try:
        with jax.enable_x64(False):
            layer = SelfAttentionLayer(n_in=H * DH, n_out=H * DH,
                                       num_heads=H, causal=True)
            params = jax.eval_shape(
                lambda: layer.init_params(jax.random.PRNGKey(0),
                                          jnp.bfloat16))
            on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=one_chip)
            x = jax.ShapeDtypeStruct((B, T, H * DH), jnp.bfloat16,
                                     sharding=one_chip)

            def loss(params, x):
                with jax.named_scope("attn0"):
                    y, _ = layer.forward(params, {}, x)
                return jnp.sum(y.astype(jnp.float32) ** 2)
            with AttentionPlanAudit() as audit:
                lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                    jax.tree_util.tree_map(on, params), x)
            yield (lowered.as_text(), lowered.compile().as_text(),
                   audit.plans())
    finally:
        helpers.restore_helper("attention", snap)
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_the_layer_takes_the_packed_tile(compiled_layer):
    _, _, plans = compiled_layer
    assert plans == {"packed,g=2,kb=1024,qb=1024": 1}


@pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd_dq",
                                  "flash_bwd_dkv"])
def test_the_three_mosaic_calls_lower(compiled_layer, name):
    lowered, compiled, _ = compiled_layer
    assert lowered.count("tpu_custom_call") == 3
    call = [l for l in compiled.splitlines()
            if re.match(rf"\s*%{name}\.?\d* = ", l)]
    assert len(call) == 1 and "tpu_custom_call" in call[0]
    # q-shaped operands are three-dimensional, [B, T, H·Dh]: what the
    # benchmark's attn_roofline.train reads its operations and bytes from
    assert f"bf16[{B},{T},{H * DH}]" in call[0]


def _instructions(text):
    """name → (op, elements of its first result, that result's layout,
    operand names, the line) of a compiled module's text."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)$", line)
        if not m:
            continue
        name, rest = m.groups()
        depth = 0
        for i, ch in enumerate(rest):        # the type: a shape or a tuple
            depth += (ch == "(") - (ch == ")")
            if ch == " " and depth == 0:
                break
        rtype, call = rest[:i], rest[i + 1:]
        shape = re.search(r"\w+\[([\d,]*)\](\{[^}]*\})?", rtype)
        if not shape or "(" not in call:
            continue
        dims = [int(d) for d in shape.group(1).split(",") if d]
        args = call[call.index("(") + 1:].split("), ")[0]
        out[name] = (call[:call.index("(")], int(np.prod(dims)),
                     shape.group(2) or "", re.findall(r"%([\w.\-]+)", args),
                     line.strip())
    return out


def _relayouts_next_to_flash(text, n):
    """Instructions of the compiled module that move an ``n``-element array
    through a transpose or a layout-changing copy and feed a ``flash_*``
    call or read one's result (directly, or through a tuple element or a
    bitcast). A copy between memory spaces in one layout is a prefetch."""
    instr = _instructions(text)
    flash = {name for name in instr if name.startswith("flash_")}
    assert len(flash) == 3, flash

    def through(name):              # look through views of the same bytes
        while name in instr and instr[name][0] in ("bitcast",
                                                   "get-tuple-element"):
            name = instr[name][3][0]
        return name

    def moves(name):
        op, size, layout, operands, _ = instr[name]
        if size != n or op not in ("copy", "transpose"):
            return False
        src = instr.get(through(operands[0]))
        strip = lambda l: re.sub(r"S\(\d+\)", "", l)
        return op == "transpose" or src is None or \
            strip(src[2]) != strip(layout)
    found = []
    for name, (_, _, _, operands, line) in instr.items():
        srcs = {through(o) for o in operands}
        if name in flash:
            found += [instr[s][4] for s in srcs if s in instr and moves(s)]
        elif srcs & flash and moves(name):
            found.append(line)
    return found


def test_no_relayout_of_a_q_sized_array_around_the_kernels(compiled_layer):
    _, compiled, _ = compiled_layer
    found = _relayouts_next_to_flash(compiled, B * T * H * DH)
    assert not found, "\n".join(l[:200] for l in found)


# ---- decode over the slab: one layer's decode_forward at gpt2-large.chat-open's
# shape, the streaming kernel forced (kernels/slab_attention.py)

SLOTS, GROUPS, T_MAX, LANES = 16, 10, 1024, 128


@pytest.fixture(scope="module")
def compiled_decode_layer(one_chip):
    """(compiled text, plans taken) of ``SelfAttentionLayer.decode_forward``
    over a donated ``[16, 10, 1024, 128]`` bfloat16 slab."""
    from jax.experimental.compilation_cache import compilation_cache
    from deeplearning4j_tpu.analysis import AttentionPlanAudit
    from deeplearning4j_tpu.kernels.slab_attention import \
        register_slab_attention
    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    snap = helpers.snapshot_helper("slab_attention")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    register_slab_attention(platforms=("tpu", "cpu"), interpret=False)
    try:
        with jax.enable_x64(False):
            d = GROUPS * LANES
            layer = SelfAttentionLayer(n_in=d, n_out=d, num_heads=d // 64,
                                       causal=True)
            on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=one_chip)
            params = jax.tree_util.tree_map(on, jax.eval_shape(
                lambda: layer.init_params(jax.random.PRNGKey(0),
                                          jnp.bfloat16)))
            cache = jax.tree_util.tree_map(on, jax.eval_shape(
                lambda: layer.init_cache(SLOTS, T_MAX, jnp.bfloat16)))
            assert cache["k"].shape == (SLOTS, GROUPS, T_MAX, LANES)
            x = jax.ShapeDtypeStruct((SLOTS, 1, d), jnp.bfloat16,
                                     sharding=one_chip)
            pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32,
                                       sharding=one_chip)

            def step(params, x, cache, pos):
                with jax.named_scope("attn0"):
                    return layer.decode_forward(params, x, cache, pos)
            with AttentionPlanAudit() as audit:
                lowered = jax.jit(step, donate_argnums=(2,)).lower(
                    params, x, cache, pos)
            yield lowered.compile().as_text(), audit.plans()
    finally:
        helpers.restore_helper("slab_attention", snap)
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_the_decode_layer_streams_the_slab(compiled_decode_layer):
    compiled, plans = compiled_decode_layer
    assert plans == {"slab_stream,g=2,hb=10,tb=512": 1}
    call = [l for l in compiled.splitlines()
            if re.match(r"\s*%slab_decode_attn\.?\d* = ", l)]
    assert len(call) == 1 and "tpu_custom_call" in call[0]
    # K and V as stored are the call's operands
    assert call[0].count(f"bf16[{SLOTS},{GROUPS},{T_MAX},{LANES}]") >= 2


def test_no_whole_layer_read_outside_the_kernel(compiled_decode_layer):
    """No ``copy`` / ``copy-start`` / ``slice-start`` that moves a layer's K
    or V (or a quarter of one, as the einsum body's prefetches did): the
    kernel's own pipeline is the one read of the slab."""
    import sys
    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from perf_kernel_checks import slab_relayout_copies
    compiled, _ = compiled_decode_layer
    shape = (SLOTS, GROUPS, T_MAX, LANES)
    assert slab_relayout_copies(compiled, shape) == []
    quarter = int(np.prod(shape)) // 4
    reads = [line for name, (op, size, _, _, line)
             in _instructions(compiled).items()
             if op in ("copy", "copy-start", "slice-start")
             and size >= quarter]
    assert not reads, "\n".join(l[:200] for l in reads)


# ---- the cells' own programs, traced only (no topology, nothing lowered):
# how many of a program's attention calls engage the packed tile

@pytest.fixture()
def traced_with_kernels():
    """Tracing picks the attention path by the default backend (the CPU
    here): steer it to the flash helper the chip takes. Nothing is lowered,
    so the compiled-kernel setting never meets the CPU."""
    from deeplearning4j_tpu.kernels.pallas_attention import \
        register_pallas_flash_attention
    from deeplearning4j_tpu.nn import helpers
    snap = helpers.snapshot_helper("attention")
    register_pallas_flash_attention(platforms=("tpu", "cpu"),
                                    interpret=False)
    with jax.enable_x64(False):
        yield
    helpers.restore_helper("attention", snap)


def _cell(config, traffic=None):
    """(family, configuration, traffic mix) of a cell, as the benchmark's
    own tests find them."""
    import json
    import sys
    bench_tests = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "benchmark")
    if bench_tests not in sys.path:
        sys.path.insert(0, bench_tests)
    import tiny

    def load(kind, name):
        with open(os.path.join(tiny.ROOT, "benchmark", kind,
                               name + ".json")) as f:
            return json.load(f)
    config = load("configs", config)
    return tiny.family(config), config, \
        load("traffic", traffic) if traffic else None


def _shapes(tree, dtype=None):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype if dtype is not None
            and a.dtype == jnp.float32 else a.dtype), tree)


def test_every_attention_call_of_the_training_cell_is_packed(
        traced_with_kernels):
    from deeplearning4j_tpu.analysis import AttentionPlanAudit
    family, config, traffic = _cell("gpt2-medium", "train-t1024")
    net, _, (params, state, upd) = family.make_net(config)
    ids = jax.ShapeDtypeStruct((traffic["batch_rows"], traffic["seq_len"]),
                               jnp.int32)
    with AttentionPlanAudit() as audit:
        net._get_train_step(False).trace(
            _shapes(params), _shapes(upd), _shapes(state), {"tokens": ids},
            {"out": ids}, None, None, 0, {})
    assert audit.plans() == {"packed,g=2,kb=1024,qb=1024": 24}
    assert audit.calls("packed") == audit.calls() == 24


def test_no_attention_call_of_chat_2k_packs(traced_with_kernels):
    """Dh 192: the latent layer's decompressed prefill keeps the folded
    [BH, T, Dh] operands, one call a layer."""
    from deeplearning4j_tpu.analysis import AttentionPlanAudit
    from deeplearning4j_tpu.models import TransformerDecoder
    family, config, _ = _cell("joyai-llm-flash")
    net, _, (params, state, _) = family.make_net(config)
    net.params = params = _shapes(params, jnp.bfloat16)
    eng = config["run"]["engine"]
    dec = TransformerDecoder(net, t_max=eng["t_max"])
    caches = jax.eval_shape(lambda: dec.init_cache(eng["num_slots"]))
    m = 4
    dec._fn("prefill_slots")
    vec = lambda dt: jax.ShapeDtypeStruct((m,), dt)
    with AttentionPlanAudit() as audit:
        dec._cost_seam["prefill_slots_impl"][0].trace(
            params, _shapes(state), caches,
            jax.ShapeDtypeStruct((m, 2048), jnp.int32), vec(jnp.int32),
            vec(jnp.int32), vec(jnp.float32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    assert audit.calls("packed") == 0
    assert audit.plans() == {"folded,g=1,kb=1024,qb=1024":
                             config["num_hidden_layers"]}


# ---- the serving cells' decode blocks, traced only: which of them stream
# the slab through kernels/slab_attention.py

@pytest.fixture()
def traced_with_slab_kernel():
    """The ``slab_attention`` helper as the chip registers it, for a trace
    on the CPU (nothing is lowered)."""
    from deeplearning4j_tpu.kernels.slab_attention import \
        register_slab_attention
    from deeplearning4j_tpu.nn import helpers
    snap = helpers.snapshot_helper("slab_attention")
    register_slab_attention(platforms=("tpu", "cpu"), interpret=False)
    with jax.enable_x64(False):
        yield
    helpers.restore_helper("slab_attention", snap)


def _decode_block_plans(config_name):
    """Plans of one traced ``decode_block4_impl`` at a cell's shapes."""
    from deeplearning4j_tpu.analysis import AttentionPlanAudit
    from deeplearning4j_tpu.models import TransformerDecoder
    family, config, _ = _cell(config_name)
    net, _, (params, state, _) = family.make_net(config)
    net.params = params = _shapes(params, jnp.bfloat16)
    eng = config["run"]["engine"]
    slots = eng["num_slots"]
    dec = TransformerDecoder(net, t_max=eng["t_max"])
    caches = jax.eval_shape(lambda: dec.init_cache(slots))
    dec._fn(("block", 4))
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    with AttentionPlanAudit() as audit:
        dec._cost_seam["decode_block4_impl"][0].trace(
            params, _shapes(state), caches, vec(jnp.int32), vec(jnp.int32),
            vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)), scalar, scalar)
    return audit.plans(), config


def test_every_slab_call_of_chat_opens_decode_block_streams(
        traced_with_slab_kernel):
    """36 of 36: the block scans its four steps over one traced body."""
    plans, config = _decode_block_plans("gpt2-large")
    assert plans == {"slab_stream,g=2,hb=10,tb=512": config["n_layer"]}


def test_no_slab_call_of_chat_2k_streams(traced_with_slab_kernel):
    """The latent layer's absorbed decode never comes through
    ``_slab_attend``."""
    plans, _ = _decode_block_plans("joyai-llm-flash")
    assert not any(k.startswith("slab_") for k in plans), plans
