"""The streaming decode-attention kernel (kernels/slab_attention.py) against
the einsum body of ``SelfAttentionLayer._slab_attend``, in Pallas interpret
mode at tiny sizes: parity over head packings, slab types, query positions
and windows, with the online softmax crossing position tiles; the helper's
declines, recorded as ``slab_einsum``; and a tiny decoder whose fused decode
block, verify window and meshed twin give the same greedy tokens with the
helper forced on and with it disabled."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import AttentionPlanAudit
from deeplearning4j_tpu.kernels import slab_attention as sa
from deeplearning4j_tpu.models import (TransformerDecoder,
                                       generate as nocache_generate,
                                       transformer_lm_conf)
from deeplearning4j_tpu.nn import helpers
from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph

T, TB = 384, 128                      # three position tiles


@pytest.fixture()
def forced_on():
    """The helper registered for the CPU too, its kernel interpreted."""
    snap = helpers.snapshot_helper("slab_attention")
    sa.register_slab_attention(platforms=("tpu", "cpu"), interpret=True)
    yield
    helpers.restore_helper("slab_attention", snap)


@pytest.fixture()
def small_tiles(monkeypatch):
    """Tiles of ``TB`` positions, one head group a block: a T of 384 is
    walked in three steps a head group."""
    monkeypatch.setattr(sa, "TILES", (TB,))
    monkeypatch.setattr(sa, "BLOCK_BYTES", 0)


def _layer(g, heads=None):
    dh = 128 // g
    heads = heads or 2 * g
    return SelfAttentionLayer(n_in=heads * dh, n_out=heads * dh,
                              num_heads=heads, causal=True), heads, dh


def _operands(g, dtype, window, qpos0, t=T, slots=2, seed=0):
    layer, heads, dh = _layer(g)
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    q = mk(slots, window, heads, dh)
    ck, cv = (mk(slots, heads // g, t, g * dh) for _ in range(2))
    qpos = jnp.minimum(
        jnp.asarray(qpos0, jnp.int32)[:, None]
        + jnp.arange(window, dtype=jnp.int32)[None, :], t - 1)
    return layer, q, ck, cv, qpos


def _both(layer, q, ck, cv, qpos):
    """(einsum body's output, helper's output, plans the helper's took)."""
    helpers.disable_helper("slab_attention")
    try:
        want = layer._slab_attend(q, ck, cv, qpos)
    finally:
        helpers.enable_helper("slab_attention")
    with AttentionPlanAudit() as audit:
        got = layer._slab_attend(q, ck, cv, qpos)
    return want, got, audit.plans()


def _close(got, want, dtype):
    assert got.dtype == want.dtype and got.shape == want.shape
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# 0, mid-tile, a tile's last cell, the next tile's first, T - 1
QPOS = [0, 70, TB - 1, TB, T - 1]


@pytest.mark.parametrize("qpos", QPOS)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_kernel_matches_einsum_body(forced_on, small_tiles, g, dtype, qpos):
    layer, q, ck, cv, pos = _operands(g, dtype, 1, [qpos, T - 1 - qpos])
    want, got, plans = _both(layer, q, ck, cv, pos)
    assert plans == {f"slab_stream,g={g},hb=1,tb={TB}": 1}
    _close(got, want, dtype)


@pytest.mark.parametrize("window", [4, 8])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_a_short_window_rides_the_same_body(forced_on, small_tiles, g,
                                            window):
    """Each query of a verify window under its own position's mask, the
    window straddling a tile's edge."""
    layer, q, ck, cv, pos = _operands(g, jnp.float32, window,
                                      [TB - 2, T - window])
    want, got, plans = _both(layer, q, ck, cv, pos)
    assert plans == {f"slab_stream,g={g},hb=1,tb={TB}": 1}
    _close(got, want, jnp.float32)


def test_several_head_groups_to_a_block(forced_on, monkeypatch):
    """The plan's own choice: every head group of a slot in one block,
    the largest tile that divides T."""
    monkeypatch.setattr(sa, "TILES", (256, TB))
    layer, heads, dh = _layer(2, heads=8)
    rng = np.random.default_rng(1)
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    q, ck, cv = mk(2, 1, heads, dh), mk(2, 4, 512, 128), mk(2, 4, 512, 128)
    want, got, plans = _both(layer, q, ck, cv,
                             jnp.asarray([[300], [511]], jnp.int32))
    assert plans == {"slab_stream,g=2,hb=4,tb=256": 1}
    _close(got, want, jnp.bfloat16)


def test_plan_at_the_cells_shape():
    """gpt2-large.chat-open: a slot's ten head groups × 1024 positions a
    grid step, 2.6 MB of K and of V."""
    assert sa.plan(1, 10, 1024, 128, jnp.bfloat16) == (10, 1024)
    assert sa.plan(1, 10, 2560, 128, jnp.bfloat16) == (10, 512)
    assert sa.plan(1, 64, 1024, 128, jnp.bfloat16) == (16, 1024)


DECLINES = [
    pytest.param(dict(lanes=64, heads=3), id="row-not-whole-lanes"),
    pytest.param(dict(t=200), id="T-no-tile-divides"),
    pytest.param(dict(window=16), id="window-too-long"),
    pytest.param(dict(qdtype=jnp.float32), id="queries-of-another-type"),
]


@pytest.mark.parametrize("case", DECLINES)
def test_helper_declines_and_the_einsum_body_runs(forced_on, case):
    lanes, t = case.get("lanes", 128), case.get("t", 256)
    window, heads = case.get("window", 1), case.get("heads", 2)
    layer = SelfAttentionLayer(n_in=heads * lanes, n_out=heads * lanes,
                               num_heads=heads, causal=True)
    assert layer.heads_per_row() == 1
    rng = np.random.default_rng(2)
    mk = lambda dt, *shape: jnp.asarray(rng.normal(size=shape), dt)
    q = mk(case.get("qdtype", jnp.bfloat16), 2, window, heads, lanes)
    ck, cv = (mk(jnp.bfloat16, 2, heads, t, lanes) for _ in range(2))
    qpos = jnp.full((2, window), 100, jnp.int32)
    want, got, plans = _both(layer, q, ck, cv, qpos)
    assert plans == {"slab_einsum": 1}
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_no_helper_on_this_backend_by_default():
    """Discovery registers the kernel for the TPU only: on the CPU the
    layer's own body runs, and says so."""
    assert helpers.get_helper("slab_attention") is None
    layer, q, ck, cv, pos = _operands(2, jnp.float32, 1, [5, 9], t=128)
    with AttentionPlanAudit() as audit:
        layer._slab_attend(q, ck, cv, pos)
    assert audit.plans() == {"slab_einsum": 1}


# ---- a tiny decoder: the same greedy tokens with the helper and without

def _tiny_lm(**kw):
    kw.setdefault("d_model", 128)
    kw.setdefault("num_heads", 2)
    kw.setdefault("num_layers", 2)
    kw.setdefault("max_length", T)
    return ComputationGraph(transformer_lm_conf(12, seed=5, **kw)).init()


def _decode(net, prompts, new, mesh=None):
    """(greedy tokens a prompt through prefill and fused blocks of 4, the
    plans the decode block traced)."""
    dec = TransformerDecoder(net, mesh=mesh)
    with AttentionPlanAudit() as audit:
        out = dec.generate(prompts, new, temperature=0.0, block_size=4)
    return [np.asarray(o) for o in out], audit.plans()


@pytest.mark.parametrize("heads,g", [(2, 2), (4, 4), (1, 1)])
def test_decode_block_tokens_with_the_helper_and_without(forced_on,
                                                         small_tiles, heads,
                                                         g):
    net = _tiny_lm(num_heads=heads)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 12, n) for n in (5, 140, 3)]
    refs = [nocache_generate(net, p, 9, temperature=0) for p in prompts]
    helpers.disable_helper("slab_attention")
    try:
        plain, plans = _decode(net, prompts, 9)
    finally:
        helpers.enable_helper("slab_attention")
    assert plans.get("slab_einsum") == 2 and not any(
        k.startswith("slab_stream") for k in plans)
    streamed, plans = _decode(net, prompts, 9)
    # the block scans its four steps over one traced body: a call a layer
    assert plans.get(f"slab_stream,g={g},hb=1,tb={TB}") == 2
    assert "slab_einsum" not in plans
    for a, b, want in zip(plain, streamed, refs):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, want)


def test_verify_window_tokens_with_the_helper(forced_on, small_tiles):
    """A speculative verify window (C = 4) through the kernel accepts the
    model's own continuation, as through the einsum body."""
    net = _tiny_lm()
    dec = TransformerDecoder(net)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 12, n) for n in (6, 131)]
    refs = [nocache_generate(net, p, 8, temperature=0) for p in prompts]
    width = 144
    tokens = np.zeros((2, width), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    nxt, _, caches = dec.prefill(dec.init_cache(2), tokens, lengths)
    draft = np.stack([r[len(p) + 1:len(p) + 4]
                      for r, p in zip(refs, prompts)])
    with AttentionPlanAudit() as audit:
        out, _, pos, _, _ = dec.verify_block(caches, np.asarray(nxt),
                                             lengths, draft)
    assert audit.plans() == {f"slab_stream,g=2,hb=1,tb={TB}": 2}
    out = np.asarray(out)
    for i, (r, p) in enumerate(zip(refs, prompts)):
        assert out[i, 4] == 4
        np.testing.assert_array_equal(out[i, :4],
                                      r[len(p) + 1:len(p) + 5])
    np.testing.assert_array_equal(np.asarray(pos), lengths + 4)


@pytest.mark.parametrize("shape", [(2, 1), (1, 2), (2, 2)],
                         ids=["data2", "tp2", "2x2"])
def test_meshed_decoder_runs_the_kernel_in_a_shard_map(forced_on,
                                                       small_tiles, shape):
    """Slots over data, head groups over tp: each device streams its own
    block of the slab, no collective."""
    from deeplearning4j_tpu.parallel.mesh import generation_mesh
    net = _tiny_lm(d_model=256, num_heads=4)         # Dh 64, g 2, H/g 2
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 12, n) for n in (4, 133)]
    plain, _ = _decode(net, prompts, 6)
    helpers.disable_helper("slab_attention")
    try:
        want, _ = _decode(net, prompts, 6)
    finally:
        helpers.enable_helper("slab_attention")
    meshed, plans = _decode(net, prompts, 6,
                            mesh=generation_mesh(*shape))
    # a call a layer and trace (a meshed block may be traced twice)
    assert plans.get(f"slab_stream,g=2,hb=1,tb={TB}") in (2, 4)
    assert "slab_einsum" not in plans
    for a, b, c in zip(want, plain, meshed):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
