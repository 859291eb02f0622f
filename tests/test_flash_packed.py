"""The lane-dense flash-attention tile (kernels/pallas_attention.py, PR 30)
in Pallas interpret mode on the CPU: heads that pack (Dh 64: two, Dh 32:
four to a 128-lane block) run on [B, T, H·Dh] operands with no transpose;
heads that do not (Dh 192, an odd head count) fold to [BH, T, Dh] through
the same kernels. Output and all three gradients against the materialized
softmax; the plan each call took is read from the record."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels.pallas_attention import (
    heads_per_tile, make_pallas_flash_helper, pallas_flash_attention)
from deeplearning4j_tpu.analysis import AttentionPlanAudit


def _materialized(q, k, v, causal, key_mask):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, -1e30)
    if causal:
        i = jnp.arange(q.shape[1])
        s = jnp.where(i[:, None] >= i[None, :], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _qkv(shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=shape) * 0.5, dtype)
            for _ in range(3)]


# (id, B, T, H, Dh, causal, masked, q_block, k_block, plan the call takes)
CASES = [
    # T 1024 in 512-square grid tiles: a skipped tile (0, 1), an interior
    # one (1, 0) and two on the diagonal, each worked in 256-row blocks
    ("g2-t1024-causal-512", 1, 1024, 2, 64, True, False, 512, 512,
     "packed,g=2,kb=512,qb=512"),
    ("g2-t1024-causal-masked-512", 1, 1024, 2, 64, True, True, 512, 512,
     "packed,g=2,kb=512,qb=512"),
    # at the blocks the kernel chooses: one tile, the diagonal through it
    ("g2-t1024-causal", 1, 1024, 2, 64, True, False, None, None,
     "packed,g=2,kb=1024,qb=1024"),
    ("g2-t1024-causal-masked", 1, 1024, 2, 64, True, True, None, None,
     "packed,g=2,kb=1024,qb=1024"),
    ("g2-t512-full", 1, 512, 2, 64, False, False, 256, 256,
     "packed,g=2,kb=256,qb=256"),
    ("g2-t512-full-masked", 2, 512, 2, 64, False, True, 256, 256,
     "packed,g=2,kb=256,qb=256"),
    ("g4-t1024-causal-masked", 1, 1024, 4, 32, True, True, 512, 512,
     "packed,g=4,kb=512,qb=512"),
    ("g4-t256-full", 2, 256, 8, 32, False, False, 128, 128,
     "packed,g=4,kb=128,qb=128"),
    # T the blocks do not divide: padded, causally or behind a made mask
    ("g2-t600-causal", 1, 600, 2, 64, True, False, 512, 512,
     "packed,g=2,kb=512,qb=512"),
    ("g2-t200-full", 1, 200, 4, 64, False, False, 128, 128,
     "packed,g=2,kb=128,qb=128"),
    # unequal blocks: the diagonal's offset inside a crossed tile is traced
    ("g2-t512-causal-256x128", 1, 512, 2, 64, True, True, 256, 128,
     "packed,g=2,kb=128,qb=256"),
    ("g2-t512-causal-128x256", 1, 512, 2, 64, True, False, 128, 256,
     "packed,g=2,kb=256,qb=128"),
    # a whole 128-lane head: lane blocks of one head, no fold either
    ("g1-dh128-causal", 1, 256, 2, 128, True, True, 128, 128,
     "packed,g=1,kb=128,qb=128"),
    # heads that do not pack fall back to the folded operands
    ("odd-heads-folded", 1, 256, 3, 64, True, True, 128, 128,
     "folded,g=1,kb=128,qb=128"),
    ("dh192-folded", 1, 256, 2, 192, True, False, 128, 128,
     "folded,g=1,kb=128,qb=128"),
    ("dh192-folded-full-masked", 1, 256, 2, 192, False, True, 128, 128,
     "folded,g=1,kb=128,qb=128"),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_output_and_gradients_match_the_materialized_softmax(case):
    _, b, t, h, d, causal, masked, qb, kb, plan = case
    q, k, v = _qkv((b, t, h, d))
    km = None
    if masked:
        m = np.ones((b, t), np.float32)
        m[0, t - t // 5:] = 0.0              # a ragged row; key 0 visible
        km = jnp.asarray(m)

    def flash(q, k, v):
        return pallas_flash_attention(q, k, v, causal=causal, q_block=qb,
                                      k_block=kb, key_mask=km,
                                      interpret=True)
    with AttentionPlanAudit() as audit:
        got = flash(q, k, v)
    assert audit.plans() == {plan: 1}
    want = _materialized(q, k, v, causal, km)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    weight = jnp.asarray(np.random.default_rng(1).normal(size=got.shape),
                         jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(flash(*a) * weight),
                     argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: jnp.sum(
        _materialized(*a, causal, km) * weight), argnums=(0, 1, 2))(q, k, v)
    for name, x, y in zip("qkv", grads, wants):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=2e-5, err_msg="d" + name)


@pytest.mark.parametrize("dh,h", [(64, 2), (32, 4)])
def test_bfloat16_operands_keep_float32_softmax(dh, h):
    """bfloat16 in, bfloat16 out, the scale on the q tile (0.125: exact):
    as close to the float32 reference as bfloat16 products allow."""
    q, k, v = _qkv((1, 512, h, dh), jnp.bfloat16)
    got = pallas_flash_attention(q, k, v, causal=True, q_block=256,
                                 k_block=256, interpret=True)
    assert got.dtype == jnp.bfloat16
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = _materialized(*f32, True, None)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 2e-2
    g = jax.grad(lambda *a: jnp.sum(pallas_flash_attention(
        *a, causal=True, q_block=256, k_block=256,
        interpret=True).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(lambda *a: jnp.sum(_materialized(*a, True, None) ** 2),
                  argnums=(0, 1, 2))(*f32)
    for x, y in zip(g, gw):
        err = float(jnp.max(jnp.abs(x.astype(jnp.float32) - y)))
        assert err < 3e-2 * float(jnp.max(jnp.abs(y)))


def _q_sized_transposes(jaxpr, n):
    """Transposes of an array of ``n`` elements in a jaxpr, looking through
    every sub-jaxpr except a kernel's own body."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "transpose" and \
                int(np.prod(eqn.outvars[0].aval.shape)) == n:
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _q_sized_transposes(sub, n)
    return found


@pytest.mark.parametrize("dh,transposes", [(64, 0), (192, 8)])
def test_packed_heads_are_never_transposed(dh, transposes):
    """Dh 64: the jaxpr of forward and backward holds no transpose of a
    q-sized array. Dh 192 is what it was: q, k, v folded and o unfolded,
    dO folded and dq, dk, dv unfolded."""
    q, k, v = _qkv((2, 256, 4, dh))

    def loss(q, k, v):
        return jnp.sum(pallas_flash_attention(
            q, k, v, causal=True, q_block=128, k_block=128,
            interpret=True) ** 2)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert _q_sized_transposes(jaxpr.jaxpr, q.size) == transposes


def test_heads_per_tile_follows_the_slab_rule():
    assert heads_per_tile(16, 64) == 2 and heads_per_tile(20, 64) == 2
    assert heads_per_tile(8, 32) == 4 and heads_per_tile(4, 128) == 1
    assert heads_per_tile(3, 64) == 0          # an odd head count
    assert heads_per_tile(32, 192) == 0        # does not divide a row
    assert heads_per_tile(6, 32) == 0 and heads_per_tile(2, 256) == 0


def test_routing_is_read_from_the_plan_record():
    """Every attention call of a traced program leaves its plan: the helper
    sends long sequences to the flash kernels (packed where heads pack),
    tile-aligned short ones to the short-T kernels, and the layer's own
    softmax reports the rest."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn import helpers

    class Conf:
        causal = True
    helper = make_pallas_flash_helper(min_seq_len=512, q_block=512,
                                      k_block=512, interpret=True)
    z = lambda *s: jnp.zeros(s, jnp.float32)
    with AttentionPlanAudit() as audit:
        assert helper(Conf(), *[z(1, 512, 2, 64)] * 3, None) is not None
        assert helper(Conf(), *[z(1, 512, 2, 192)] * 3, None) is not None
        assert helper(Conf(), *[z(1, 256, 2, 64)] * 3, None) is not None
        assert helper(Conf(), *[z(1, 64, 2, 64)] * 3, None) is None
    assert audit.plans() == {"packed,g=2,kb=512,qb=512": 1,
                             "folded,g=1,kb=512,qb=512": 1, "short": 1}
    assert audit.calls("packed") == 1 and audit.calls() == 3
    # a layer traced twice over with no helper: two materialized calls
    layer = SelfAttentionLayer(n_in=16, n_out=16, num_heads=2, causal=True)
    params = layer.init_params(jax.random.PRNGKey(0))
    snap = helpers.snapshot_helper("attention")
    helpers.disable_helper("attention")
    try:
        with AttentionPlanAudit() as audit:
            jax.make_jaxpr(lambda x: layer.forward(
                params, {}, layer.forward(params, {}, x)[0])[0])(z(1, 8, 16))
        assert audit.plans() == {"materialized": 2}
    finally:
        helpers.restore_helper("attention", snap)
