"""A fingerprint of the lowered text of every serving program of
``TransformerDecoder`` at tiny shapes: what a refactor of the decoder or of
the layers it walks shows before any chip time is spent. Nothing runs at
size; no timing is taken.

    JAX_PLATFORMS=cpu python scripts/program_fingerprints.py OUT.json [DIR]

It reads the tree it lies in. Copy it into the parent's ``scripts/`` too
(``git archive <commit> | tar -x -C <dir>``), run both, and compare the two
JSON files: equal fingerprints are equal StableHLO texts
(``.lower(...).as_text()`` carries no source locations), so equal programs
for the compiler and for the persistent compile cache. ``DIR`` receives the
texts themselves, to ``diff`` the ones that differ.

Models: GPT-2-shaped in float32 (heads too narrow to pack) and in bfloat16
with two 64-wide heads to a cache row; latent attention + routed experts;
plain attention with one routed-experts layer; the shortcut-connected
double block. Each with the sentinel off and on; the paged programs where
the model has a paged pool. Programs: ``prefill``, ``step``,
``prefill_slots``, ``("block", 1 | 4)``, ``("chunk", 8)``, ``("verify",
4)``, ``paged_prefill``, ``("paged_block", 1 | 4)``, ``("paged_verify",
4)`` from the signatures the cost seam records at one dispatch, and
``recompute`` (jitted outside ``_fn``); and the training step of the
bfloat16 GPT-2 on sparse labels (``gpt2-bf16-g2|train``)."""

import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from deeplearning4j_tpu.models import (                       # noqa: E402
    TransformerDecoder, latent_moe_lm_conf, shortcut_moe_lm_conf,
    transformer_lm_conf)
from deeplearning4j_tpu.nn.conf.layers import RoutedExpertsLayer  # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph     # noqa: E402

V, T_MAX, B, PS = 97, 64, 4, 8


def gpt2(dtype, d=32, heads=4):
    return ComputationGraph(
        transformer_lm_conf(V, d, heads, 2, max_length=T_MAX),
        compute_dtype=dtype).init()


def latent():
    return ComputationGraph(latent_moe_lm_conf(
        V, 32, 4, 3, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
        dense_hidden=64, num_experts=8, top_k=2, expert_hidden=16,
        routed_scaling=2.5, max_length=T_MAX, rope_theta=1e4)).init()


def mixed():
    conf = transformer_lm_conf(V, 32, 4, 2, max_length=T_MAX)
    conf.vertices["ffn1"].layer = RoutedExpertsLayer(
        n_in=32, n_out=32, num_experts=4, top_k=2, expert_hidden=16)
    return ComputationGraph(conf).init()


def shortcut():
    return ComputationGraph(shortcut_moe_lm_conf(
        V, 32, 4, 2, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
        dense_hidden=64, num_experts=8, zero_experts=4, top_k=3,
        expert_hidden=16, routed_scaling=6.0, first_expert=0,
        experts_held=4, q_scale=1.5, kv_scale=2.0, max_length=T_MAX,
        rope_theta=1e4)).init()


MODELS = (("gpt2-f32", lambda: gpt2(jnp.float32), True),
          ("gpt2-bf16-g2", lambda: gpt2(jnp.bfloat16, 128, 2), True),
          ("latent-moe", latent, False),
          ("mixed", mixed, True),
          ("shortcut-moe", shortcut, False))


def train_text(net):
    """The lowered training step on sparse labels (fused CE), as the
    training cell runs it."""
    step = net._get_train_step()
    toks = jnp.zeros((2, 16), jnp.int32)
    return step.lower(net.params, net.updater_state, net.state,
                      {"tokens": toks}, {"out": toks}, None, None, 0,
                      None).as_text()


def drive(dec, paged):
    """Dispatch every program once, so the cost seam has its signature."""
    params, state = dec._device_params(), dec.net._inference_state()
    key = jax.random.PRNGKey(0)
    toks = jnp.zeros((B, 16), jnp.int32)
    lens = jnp.full((B,), 9, jnp.int32)
    z = jnp.zeros((B,), jnp.int32)
    temps = jnp.zeros((B,), jnp.float32)
    draft = np.zeros((B, 4), np.int32)
    _, _, caches = dec.prefill(dec.init_cache(B), toks, lens)
    _, _, caches = dec.decode_step(caches, z, lens)
    _, _, caches = dec._fn("prefill_slots")(
        params, state, caches, toks[:2], lens[:2],
        jnp.arange(2, dtype=jnp.int32), temps[:2], key)
    for k in (1, 4):
        caches = dec.decode_block(caches, z, lens, block_size=k)[-1]
    _, caches = dec._fn(("chunk", 8))(
        params, state, caches, toks[:1, :8], z[:1],
        jnp.full((1,), 8, jnp.int32), z[:1], temps[:1], key, z[:1])
    dec.verify_block(caches, z, lens, draft)
    dec.recompute_logits(toks, lens)
    if paged:
        pool = dec.init_paged_pool(B * T_MAX // PS + 1, PS)
        tables = jnp.asarray(
            1 + np.arange(B * T_MAX // PS).reshape(B, -1), jnp.int32)
        _, pool = dec.paged_prefill(pool, toks, z, lens, tables)
        for k in (1, 4):
            pool = dec.paged_decode_block(pool, tables, z, lens,
                                          block_size=k)[-1]
        dec.paged_verify_block(pool, tables, z, lens, draft)


def texts(dec):
    out = {name: entry[0].lower(*entry[1]).as_text()
           for name, entry in dec._cost_seam.items() if entry[1] is not None}
    out["recompute"] = dec._jit["recompute"].lower(
        dec._device_params(), dec.net._inference_state(),
        jnp.zeros((B, 16), jnp.int32), jnp.full((B,), 9, jnp.int32),
        jnp.zeros((B,), jnp.float32), jax.random.PRNGKey(0)).as_text()
    return out


def main():
    out_path = sys.argv[1]
    dump = sys.argv[2] if len(sys.argv) > 2 else None
    table = {}
    for model, make, paged in MODELS:
        net = make()
        for sentinel in (False, True):
            dec = TransformerDecoder(net, t_max=T_MAX, sentinel=sentinel)
            drive(dec, paged)
            for name, text in texts(dec).items():
                key = f"{model}|s{int(sentinel)}|{name}"
                table[key] = hashlib.sha256(text.encode()).hexdigest()[:16]
                if dump:
                    os.makedirs(dump, exist_ok=True)
                    with open(os.path.join(
                            dump, key.replace("|", "__") + ".mlir"),
                            "w", encoding="utf-8") as f:
                        f.write(text)
    text = train_text(gpt2(jnp.bfloat16, 128, 2))
    table["gpt2-bf16-g2|train"] = hashlib.sha256(
        text.encode()).hexdigest()[:16]
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    print(len(table), "programs")


if __name__ == "__main__":
    main()
