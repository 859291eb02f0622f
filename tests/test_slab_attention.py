"""The streaming decode-attention kernel (kernels/slab_attention.py) against
the einsum body of ``SelfAttentionLayer._slab_attend``, in Pallas interpret
mode at tiny sizes: parity over head packings, slab types, query positions
and windows, with the online softmax crossing position tiles; what it reads
(NaN past a slot's last live tile, and in a stopped lane's whole row, never
reaches an output that counts); the tile rule; the helper's declines,
recorded as ``slab_einsum``; a tiny decoder whose fused decode block, verify
window, engine and meshed twin give the same greedy tokens with the helper
forced on and with it disabled; and the positions the decode steps read,
counted by hand."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import AttentionPlanAudit
from deeplearning4j_tpu.kernels import slab_attention as sa
from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder,
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
    """The smallest tile of at least 256 positions whose K block holds at
    least 1 MiB: gpt2-large.chat-open's ten head groups × 512 positions
    (1.25 MiB of K and of V a grid step), Granite's four × 1024 (1 MiB);
    where no tile's block reaches it, the largest tile that divides T."""
    assert sa.plan(1, 10, 1024, 128, jnp.bfloat16) == (10, 512)
    assert sa.plan(1, 4, 2048, 128, jnp.bfloat16) == (4, 1024)
    assert sa.plan(1, 10, 2560, 128, jnp.bfloat16) == (10, 512)
    assert sa.plan(1, 64, 1024, 128, jnp.bfloat16) == (64, 256)
    assert sa.plan(1, 1, 1024, 128, jnp.bfloat16) == (1, 1024)
    assert sa.plan(4, 4, 2048, 128, jnp.float32) == (4, 512)


def _poisoned(ck, cv, last, tb=TB):
    """K and V with every position past slot b's tile ``last[b]`` NaN (a
    slot at -1: its whole row)."""
    kpos = np.arange(ck.shape[2])
    bad = kpos[None, :] >= (np.asarray(last)[:, None] + 1) * tb
    bad = jnp.asarray(bad[:, None, :, None])
    return (jnp.where(bad, jnp.nan, ck).astype(ck.dtype),
            jnp.where(bad, jnp.nan, cv).astype(cv.dtype))


@pytest.mark.parametrize("qpos", [[0, 200], [TB - 1, TB], [T - 1, 70]])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_tiles_past_the_live_length_are_never_read(forced_on, small_tiles,
                                                   g, qpos):
    """NaN past each slot's last live tile: the kernel still gives what
    the einsum body gives on the clean slab (0 · NaN would be NaN)."""
    layer, q, ck, cv, pos = _operands(g, jnp.float32, 1, qpos)
    helpers.disable_helper("slab_attention")
    try:
        want = layer._slab_attend(q, ck, cv, pos)
    finally:
        helpers.enable_helper("slab_attention")
    got = layer._slab_attend(q, *_poisoned(ck, cv, np.asarray(qpos) // TB),
                             pos)
    _close(got, want, jnp.float32)


@pytest.mark.parametrize("g", [1, 2])
def test_a_lane_that_is_not_alive_reads_nothing(forced_on, small_tiles, g):
    """A stopped lane's whole row NaN: its rows come out zero, and the
    alive lanes' what the einsum body gives on the clean slab."""
    layer, q, ck, cv, pos = _operands(g, jnp.float32, 1, [300, 40, 130],
                                      slots=3)
    alive = jnp.asarray([True, False, True])
    helpers.disable_helper("slab_attention")
    try:
        want = layer._slab_attend(q, ck, cv, pos)
    finally:
        helpers.enable_helper("slab_attention")
    got = layer._slab_attend(q, *_poisoned(ck, cv, [2, -1, 1]), pos, alive)
    assert np.all(np.asarray(got[1]) == 0.0)
    _close(got[::2], want[::2], jnp.float32)


def test_a_verify_window_across_a_tile_edge_with_every_lane_counted(
        forced_on, small_tiles):
    """C = 4 queries from TB - 2 (the last two in the next tile) and from
    T - 4, no ``alive``: each slot reads up to its last query's tile, NaN
    past it."""
    layer, q, ck, cv, pos = _operands(2, jnp.float32, 4, [TB - 2, T - 4])
    helpers.disable_helper("slab_attention")
    try:
        want = layer._slab_attend(q, ck, cv, pos)
    finally:
        helpers.enable_helper("slab_attention")
    got = layer._slab_attend(q, *_poisoned(ck, cv, [1, 2]), pos)
    _close(got, want, jnp.float32)


def test_live_tiles_and_the_work_list_are_what_the_kernel_reads():
    """A slot's last tile (-1: none), and the grid's steps made of them:
    each slot's live tiles in turn, then the last live step repeated (no
    live step at all: slot B - 1's first tile, never worked)."""
    qpos = jnp.asarray([[0], [127], [128], [383], [500]], jnp.int32)
    alive = jnp.asarray([True, True, False, True, True])
    last = sa.live_tiles(qpos, alive, 128, 3)
    np.testing.assert_array_equal(last, [0, 0, -1, 2, 2])
    np.testing.assert_array_equal(sa.live_tiles(qpos, None, 256, 2),
                                  [0, 0, 0, 1, 1])
    slot, tile, n = sa.work_list(last, 15)
    assert n.tolist() == [8]
    np.testing.assert_array_equal(
        slot, [0, 1, 3, 3, 3, 4, 4, 4] + [4] * 7)
    np.testing.assert_array_equal(
        tile, [0, 0, 0, 1, 2, 0, 1, 2] + [2] * 7)
    slot, tile, n = sa.work_list(jnp.full(5, -1, jnp.int32), 15)
    assert n.tolist() == [0]
    assert set(slot.tolist()) == {4} and set(tile.tolist()) == {0}


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


def _serve(net, waves):
    """(every request's tokens, the engine's stats) of a 4-slot engine
    serving ``waves`` of (prompt, new tokens, eos) in turn, blocks of 4."""
    eng = SlotGenerationEngine(net, num_slots=4, block_size=4, seed=0)
    out = []
    for wave in waves:
        reqs = [eng.submit(p, n, eos_id=e) for p, n, e in wave]
        eng.run_until_drained()
        out += [r.result(0) for r in reqs]
    return out, eng.stats()


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
    # an engine: the long prompt's request stops on its eos in the first
    # block's second step (a lane stopped mid-block), the short ones free
    # their slots while it decodes and those keep their stale positions;
    # a second wave lands in the freed slots
    gen = refs[1][len(prompts[1]):]
    eos = int(gen[1])
    assert eos not in gen[:1]
    waves = [[(prompts[0], 3, None), (prompts[1], 9, eos),
              (prompts[2], 9, None)],
             [(prompts[2], 6, None), (prompts[0], 9, None)]]
    helpers.disable_helper("slab_attention")
    try:
        plain, plain_stats = _serve(net, waves)
    finally:
        helpers.enable_helper("slab_attention")
    streamed, stats = _serve(net, waves)
    for a, b in zip(plain, streamed):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(streamed[1], refs[1][:len(prompts[1]) + 2])
    np.testing.assert_array_equal(streamed[2], refs[2])
    assert plain_stats["slab_positions_read"] == \
        plain_stats["slab_positions_held"] == stats["slab_positions_held"]
    assert 0 < stats["slab_positions_read"] < stats["slab_positions_held"]


def test_the_counters_are_the_positions_the_kernel_reads(forced_on,
                                                         small_tiles):
    """One block of four steps over four slots of T = 384 (tiles of 128):
    an alive lane at 126 crosses into its second tile at the third step,
    one at 300 reads three tiles a step, two stopped lanes read nothing —
    2304 positions a layer of the 4 × 4 × 384 held; the einsum body reads
    what is held."""
    net = _tiny_lm()
    tokens = np.ones((4, 8), np.int32)
    lengths = np.full(4, 8, np.int32)
    positions = np.asarray([126, 5, 300, 200], np.int32)
    stopped = np.asarray([False, True, False, True])
    layers = 2
    held = 4 * 4 * T * layers
    for on, read in ((True, (128 + 128 + 256 + 256 + 4 * 384) * layers),
                     (False, held)):
        dec = TransformerDecoder(net)       # a program traced anew
        assert dec.counter_names == ("slab_positions_read",
                                     "slab_positions_held")
        (helpers.enable_helper if on else helpers.disable_helper)(
            "slab_attention")
        try:
            nxt, _, caches = dec.prefill(dec.init_cache(4), tokens, lengths)
            toks = dec.decode_block(caches, nxt, positions, stopped=stopped,
                                    block_size=4)[0]
        finally:
            helpers.enable_helper("slab_attention")
        arr, counts = dec.split_block(np.asarray(toks))
        assert arr.shape == (4, 4)
        assert counts.tolist() == [read, held]


def test_an_engine_counts_what_its_blocks_read(forced_on, small_tiles):
    """A 126-token prompt on one of four lanes, five new tokens: the one
    retired block's steps sit at 126-129 (tiles 0, 0, 1, 1 of 128), the
    three free lanes read nothing; ``stats()`` and the gauge say so."""
    net = _tiny_lm()
    eng = SlotGenerationEngine(net, num_slots=4, block_size=4, seed=0)
    req = eng.submit(np.arange(126) % 12, 5)
    eng.run_until_drained()
    assert len(req.result(0)) == 131
    st = eng.stats()
    layers = 2
    assert st["slab_positions_held"] == 4 * 4 * T * layers
    assert st["slab_positions_read"] == (128 + 128 + 256 + 256) * layers
    share = eng._registry.get("generation_slab_read_share").labels(
        eng.engine_id).value
    assert share == pytest.approx(768 / (4 * 4 * T))


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
