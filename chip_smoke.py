#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Bring-up, not a benchmark: ONE process drives the flagship LM (12 x 768,
12 heads, vocab 32k, bf16 compute, random weights from a seed) through the
entry points users call, on whatever accelerator jax finds, and fails unless
every phase is right:

  device    jax must report a TPU; no accelerator -> non-zero exit, no result
  kernels   short-T Pallas attention, COMPILED (never interpreted), forward
            and gradients against the jnp reference at the train shape
  serve     SlotGenerationEngine start()/submit()/result() — the slab engine
            and the paged engine with prefix cache, block_size 4, 16 seeded
            mixed-length requests each, served twice under CompileAudit
            (non-zero warm-up compiles, {} over the second wave), one call
            through ParallelInference.generate(), logits against the no-cache
            materialized reference, Mosaic call present in the slab prefill
  train     five ComputationGraph.fit_batch steps at B=32, T=512 with sparse
            labels; loss finite and falling; Mosaic call present in the step
  multichip (>= 4 devices) the same requests through a (2, 2) serving mesh
            and one GraphDataParallelTrainer step over four devices, with
            parameters, KV cache and batch verified to span all four

Sizes are the constants below; there are no knobs. The last line of stdout
is ``{"ok": true, "device": {...}}`` and the exit code is 0 only if every
phase passed. It prints set-up/compile seconds per phase and no throughput,
utilization or roofline figure.
"""

from __future__ import annotations

import contextlib
import dataclasses
import faulthandler
import gc
import io
import json
import os
import sys
import time
from importlib import metadata
from typing import Dict, List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    heads: int
    layers: int
    t_max: int            # serving context bound (prompt + generated)
    long_prompt: int      # one prompt is EXACTLY this long: slab prefill at
    #                       this bucket takes the short-T Pallas kernel
    shared_prefix: int    # two prompts share this many leading tokens
    num_slots: int
    block: int            # fused decode steps per device program
    page_size: int
    gen_div: int          # new-token counts are divided by this (toy runs)
    train_batch: int
    train_t: int
    train_steps: int
    lr: float
    kernel_batch: int     # kernel check runs at (kernel_batch, heads,
    #                       train_t, d_model // heads)


#: the flagship: transformer_lm_conf at the widest shape any script in the
#: repo runs, serving t_max 1024, training at the LM-train shape bench.py
#: has history for (B=32, T=512, sparse labels)
FULL = Sizes(vocab=32_000, d_model=768, heads=12, layers=12, t_max=1024,
             long_prompt=512, shared_prefix=256, num_slots=8, block=4,
             page_size=16, gen_div=1, train_batch=32, train_t=512,
             train_steps=5, lr=3e-4, kernel_batch=32)

SEED = 0
#: engine logits vs the no-cache materialized reference, as
#: max|a-b| / max|b|. Both sides compute in bf16 (8 mantissa bits, ~0.4%
#: per rounding) through 12 layers, and the two attention paths round in
#: different places (the kernel keeps softmax in f32 and rounds p once for
#: the PV matmul), so ~1% is expected; 5% still fails a wrong mask, a
#: dropped layer or a cache written at the wrong position. Tokens are NOT
#: compared across programs: on random weights the top logits sit closer
#: than this noise (bench.py _mesh_sweep records the same).
LOGITS_TOL = 5e-2
RESULT_TIMEOUT_S = 900.0
#: dump every thread's stack and exit non-zero before the driver's own
#: 1200 s limit would kill a hung run silently
WATCHDOG_S = 1150

#: request mix: prompt length in 64ths of long_prompt (None = shared prefix
#: + private tail), new tokens, temperature. Slots are 8, so rows 0-7 admit
#: as one batch; rows 0-3 finish together and free four slots for rows
#: 8-11, rows 4-7 likewise for rows 12-15 — three admission shapes per
#: engine, deterministic because everything is queued before start().
#: Row 1 registers the shared prefix; row 8 arrives a wave later and hits.
_MIX = [(64, 32, 0.0), (None, 32, 0.0), (1, 32, 0.0), (3, 32, 0.0),
        (12, 48, 0.0), (25, 48, 0.8), (8, 48, 0.0), (41, 48, 0.0),
        (None, 40, 0.0), (19, 40, 0.0), (6, 64, 0.8), (2, 64, 0.0),
        (11, 32, 0.0), (4, 32, 0.0), (1, 36, 0.0), (15, 36, 0.0)]
_PRIVATE_TAILS = (40, 17)     # tails of the two shared-prefix prompts

_platform = "?"


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[{_platform}] {msg}", flush=True)


def make_requests(sizes: Sizes) -> List[Tuple[np.ndarray, int, float]]:
    rng = np.random.default_rng(SEED)
    prefix = rng.integers(0, sizes.vocab, sizes.shared_prefix)
    tails = iter(_PRIVATE_TAILS)
    reqs = []
    for frac, new, temp in _MIX:
        if frac is None:
            prompt = np.concatenate(
                [prefix, rng.integers(0, sizes.vocab, next(tails))])
        else:
            prompt = rng.integers(
                0, sizes.vocab, max(8, sizes.long_prompt * frac // 64))
        reqs.append((prompt.astype(np.int32),
                     max(sizes.block, new // sizes.gen_div), temp))
    return reqs


def build_net(sizes: Sizes):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import transformer_lm_conf
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = transformer_lm_conf(
        vocab_size=sizes.vocab, d_model=sizes.d_model,
        num_heads=sizes.heads, num_layers=sizes.layers,
        max_length=sizes.t_max, learning_rate=sizes.lr, seed=SEED)
    return ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()


def kernel_checks():
    """scripts/perf_kernel_checks.py as a module: its attention check, its
    thresholds and its error metric (max|a-b| / max|b|) are the ones used
    here, not copies of them."""
    scripts = os.path.join(_HERE, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import perf_kernel_checks
    return perf_kernel_checks


def has_mosaic_call(jitted, *args) -> bool:
    """Whether the program ``jitted`` lowers to for ``args`` contains a
    Mosaic (compiled Pallas) custom call. Interpret-mode Pallas lowers to
    plain HLO and so reads False."""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


# ------------------------------------------------------------------ kernels
def phase_kernels(sizes: Sizes, require_mosaic: bool
                  ) -> Dict[str, Dict[str, float]]:
    """short_attention forward + dq/dk/dv against the jnp reference, causal,
    unmasked and ragged-mask, with the check and thresholds
    scripts/perf_kernel_checks.py uses. ``interpret`` is left to the
    kernel's own default — compiled on every backend but the CPU — and
    ``require_mosaic`` demands that the default really compiled it."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.kernels.pallas_shortseq import short_attention

    kc = kernel_checks()
    t0 = time.perf_counter()
    if require_mosaic:
        qkv = jax.ShapeDtypeStruct(
            (sizes.kernel_batch, sizes.train_t, sizes.heads,
             sizes.d_model // sizes.heads), jnp.bfloat16)
        check(has_mosaic_call(
            jax.jit(lambda q, k, v: short_attention(q, k, v, causal=True)),
            qkv, qkv, qkv),
            "short_attention lowered WITHOUT a Mosaic call: it would run "
            "interpreted")
    rows: List = []
    with contextlib.redirect_stdout(io.StringIO()):
        kc.check_attention(
            rows,
            lambda q, k, v, km: short_attention(q, k, v, causal=True,
                                                key_mask=km),
            f"short-T@{sizes.train_t}", b=sizes.kernel_batch,
            t=sizes.train_t, h=sizes.heads, d=sizes.d_model // sizes.heads,
            key_mask_tail=sizes.train_t // 4)
    out = {}
    for tag, errs, thresh in rows:
        say(f"kernels: {tag} " + " ".join(f"{k}={v:.2e}"
                                          for k, v in errs.items())
            + f" (threshold {thresh:.0e})")
        check(all(np.isfinite(e) and e <= thresh for e in errs.values()),
              f"{tag}: kernel disagrees with the jnp reference: {errs}")
        out[tag] = errs
    check(len(out) == 2, f"expected unmasked + masked rows, got {list(out)}")
    say(f"kernels: ok, set-up+run {time.perf_counter() - t0:.1f}s")
    return out


# -------------------------------------------------------------------- serve
def _serve_wave(net, dec, reqs, sizes: Sizes, **engine_kw):
    """One engine over the shared decoder: queue every request, THEN start
    (so admission batches are the same every wave), collect, verify."""
    from deeplearning4j_tpu.models.generation import SlotGenerationEngine
    eng = SlotGenerationEngine(net, decoder=dec, num_slots=sizes.num_slots,
                               block_size=sizes.block, seed=SEED,
                               **engine_kw)
    handles = [eng.submit(p, n, temperature=t) for p, n, t in reqs]
    eng.start()
    try:
        outs = [h.result(timeout=RESULT_TIMEOUT_S) for h in handles]
        stats = eng.stats()
        page_audit = None if eng._pager is None \
            else eng._pager.audit(eng._slot_pages)
    finally:
        eng.shutdown()
    for (p, n, _), out in zip(reqs, outs):
        check(len(out) == len(p) + n,
              f"request with prompt {len(p)} asked {n} tokens, got "
              f"{len(out) - len(p)}")
        check(np.array_equal(out[:len(p)], p), "result lost its prompt")
        check(out.min() >= 0 and out.max() < sizes.vocab,
              "generated token outside the vocabulary")
    check(stats["completed"] == len(reqs) and stats["failed"] == 0,
          f"engine stats disagree with the results: {stats}")
    return outs, stats, page_audit


def _serve_config(name: str, net, reqs, sizes: Sizes, prefill_impl: str,
                  block_impl: str, mesh=None, **engine_kw):
    """Warm-up wave + steady wave of the same requests under CompileAudit."""
    from deeplearning4j_tpu.analysis.compile_audit import CompileAudit
    from deeplearning4j_tpu.models import TransformerDecoder
    dec = TransformerDecoder(net, t_max=sizes.t_max, mesh=mesh)
    with CompileAudit() as audit:
        t0 = time.perf_counter()
        outs, stats, page_audit = _serve_wave(net, dec, reqs, sizes,
                                              **engine_kw)
        t1 = time.perf_counter()
        warm = dict(audit.report()["per_function"])
        snap = audit.snapshot()
        outs2, _, page_audit2 = _serve_wave(net, dec, reqs, sizes,
                                            **engine_kw)
        t2 = time.perf_counter()
        steady = audit.delta(snap)
    say(f"serve.{name}: {len(reqs)} requests x2 waves ok; warm-up wave "
        f"{t1 - t0:.1f}s (compile included), second wave {t2 - t1:.1f}s; "
        f"warm-up compiles {warm}; second-wave compiles {steady}")
    for impl in (prefill_impl + dec._impl_suffix,
                 block_impl + dec._impl_suffix):
        check(warm.get(impl, 0) > 0,
              f"serve.{name}: the audit saw no compile of {impl} during "
              f"warm-up ({warm}) — it is not observing this engine")
    check(steady == {}, f"serve.{name}: second wave of the same shapes "
                        f"compiled {steady}")
    check(all(np.array_equal(a, b) for a, b in zip(outs, outs2)),
          f"serve.{name}: the same requests decoded differently the second "
          "time on the same programs")
    for pa in (page_audit, page_audit2):
        check(not pa, f"serve.{name}: pager audit found {pa}")
    return dec, outs, stats


def _reference_check(dec, prompt: np.ndarray, engine_firsts, sizes: Sizes):
    """The first decoded position's logits (cache-filling prefill, kernel
    path on the chip) and the next position's (one decode step THROUGH the
    cache) against ``recompute_logits`` — the no-cache full forward — traced
    with the attention helper disabled, i.e. the materialized jnp path."""
    from deeplearning4j_tpu.nn import helpers
    n = len(prompt)
    ids, logits, caches = dec.prefill(dec.init_cache(1), prompt[None], [n])
    nxt, logits2, caches = dec.decode_step(caches, ids, [n])
    ctx2 = np.zeros((1, n + 128), np.int32)
    ctx2[0, :n] = prompt
    ctx2[0, n] = int(np.asarray(ids)[0])
    helpers.disable_helper("attention")
    try:
        _, ref = dec.recompute_logits(prompt[None], [n])
        _, ref2 = dec.recompute_logits(ctx2, [n + 1])
    finally:
        helpers.enable_helper("attention")
    del caches
    logits, logits2, ref, ref2 = (np.asarray(x)[0] for x in
                                  (logits, logits2, ref, ref2))
    check(logits.shape == (sizes.vocab,) and logits2.shape == ref2.shape,
          f"logits shape {logits.shape}, expected ({sizes.vocab},)")
    for nm, a in (("prefill", logits), ("decode", logits2),
                  ("reference", ref), ("reference+1", ref2)):
        check(np.isfinite(a).all(), f"non-finite {nm} logits")
    rel = kernel_checks().rel
    e1, e2 = rel(logits, ref), rel(logits2, ref2)
    say(f"serve.reference: prompt {n}: prefill logits vs no-cache "
        f"reference rel err {e1:.2e}; decode-through-cache logits "
        f"{e2:.2e} (tolerance {LOGITS_TOL:.0e})")
    check(e1 <= LOGITS_TOL and e2 <= LOGITS_TOL,
          f"logits disagree with the reference: {e1:.3e}, {e2:.3e}")
    # the engines' own first token for this prompt must be (within the
    # same tolerance) a maximizer of the reference logits
    slack = LOGITS_TOL * float(np.max(np.abs(ref)))
    for name, tok in engine_firsts.items():
        check(ref[tok] >= ref.max() - slack,
              f"serve.{name}: first token {tok} scores {ref[tok]:.4f} in "
              f"the reference, whose max is {ref.max():.4f}")


def _seam_mosaic(dec) -> Dict[str, bool]:
    """Per compiled serving impl (first-dispatch signature from the
    decoder's cost seam): does its lowering contain a Mosaic call?"""
    return {name: has_mosaic_call(jitted, *specs)
            for name, (jitted, specs, _) in sorted(dec._cost_seam.items())
            if specs is not None}


def phase_serve(net, sizes: Sizes, require_mosaic: bool):
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    reqs = make_requests(sizes)
    check(any(len(p) == sizes.long_prompt for p, _, _ in reqs),
          "request mix lost its exactly-long prompt")
    k = sizes.block
    slab_dec, slab_outs, _ = _serve_config(
        "slab", net, reqs, sizes, "prefill_slots_impl",
        f"decode_block{k}_impl")
    paged_dec, paged_outs, paged_stats = _serve_config(
        "paged", net, reqs, sizes, "paged_prefill_impl",
        f"paged_decode_block{k}_impl", paged=True, prefix_cache=True,
        page_size=sizes.page_size)
    check(paged_stats["prefix_cache_hits"] > 0,
          f"paged engine recorded no prefix hit: {paged_stats}")
    say(f"serve.paged: prefix_cache_hits={paged_stats['prefix_cache_hits']} "
        f"hit_tokens={paged_stats['prefix_cache_hit_tokens']}")

    t0 = time.perf_counter()
    pi = ParallelInference(net, generation_slots=sizes.num_slots,
                           generation_t_max=sizes.t_max,
                           generation_block_size=k)
    try:
        p, n, _ = reqs[2]
        out = pi.generate(p, n, timeout=RESULT_TIMEOUT_S)
    finally:
        pi.shutdown()
    check(len(out) == len(p) + n and np.array_equal(out[:len(p)], p),
          "ParallelInference.generate returned the wrong sequence")
    say(f"serve.parallel_inference: generate() ok, "
        f"{time.perf_counter() - t0:.1f}s (own decoder, compile included)")

    long_i = next(i for i, (p, _, _) in enumerate(reqs)
                  if len(p) == sizes.long_prompt)
    n = sizes.long_prompt
    _reference_check(slab_dec, reqs[long_i][0],
                     {"slab": int(slab_outs[long_i][n]),
                      "paged": int(paged_outs[long_i][n])}, sizes)

    mosaic = {**_seam_mosaic(slab_dec), **_seam_mosaic(paged_dec)}
    say(f"serve.mosaic: programs whose lowering holds a Mosaic call: "
        f"{mosaic}")
    if require_mosaic:
        check(mosaic.get("prefill_slots_impl"),
              "slab prefill at the long-prompt bucket lowered WITHOUT the "
              "short-T Pallas kernel")
    return mosaic


# -------------------------------------------------------------------- train
def _train_batch(sizes: Sizes):
    from deeplearning4j_tpu.models import lm_batch_sparse
    rng = np.random.default_rng(SEED)
    return lm_batch_sparse(rng.integers(
        0, sizes.vocab, (sizes.train_batch, sizes.train_t + 1)))


def phase_train(net, sizes: Sizes, require_mosaic: bool) -> List[float]:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.dataset import DataSet
    x, y = _train_batch(sizes)
    ds = DataSet(jax.device_put(jnp.asarray(x)),
                 jax.device_put(jnp.asarray(y)))
    step = net._get_train_step(False)
    imasks, lmasks = net._masks_of(ds)
    mosaic = has_mosaic_call(
        step, net.params, net.updater_state, net.state,
        net._inputs_dict(ds.features), net._labels_dict(ds.labels),
        imasks, lmasks, net.iteration, {})
    say(f"train.mosaic: train step lowering holds a Mosaic call: {mosaic}")
    if require_mosaic:
        check(mosaic, "train step lowered WITHOUT the short-T Pallas kernel")
    losses, secs = [], []
    for _ in range(sizes.train_steps):
        t0 = time.perf_counter()
        net.fit_batch(ds)
        losses.append(float(net.score_value))     # forces the step
        secs.append(time.perf_counter() - t0)
    say(f"train: B={sizes.train_batch} T={sizes.train_t} losses "
        + " ".join(f"{v:.4f}" for v in losses)
        + f"; first step {secs[0]:.1f}s (compile included)")
    check(all(np.isfinite(v) for v in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {sizes.train_steps} steps on one fixed "
          f"batch: {losses}")
    return losses


# ---------------------------------------------------------------- multichip
def _spans(x, n: int) -> bool:
    return len(x.sharding.device_set) == n and \
        len({s.device for s in x.addressable_shards}) == n


def phase_multichip(net, sizes: Sizes, require_mosaic: bool
                    ) -> Optional[dict]:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.analysis.compile_audit import TransferAudit
    from deeplearning4j_tpu.ops.dataset import DataSet
    from deeplearning4j_tpu.parallel.graph_wrapper import \
        GraphDataParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import generation_mesh, make_mesh
    n_dev = jax.device_count()
    if n_dev < 4:
        say(f"multichip: skipped: {n_dev} device")
        return None
    mesh = generation_mesh(2, 2)
    k = sizes.block
    with TransferAudit() as transfers:
        dec, _, _ = _serve_config(
            "mesh2x2", net, make_requests(sizes), sizes,
            "prefill_slots_impl", f"decode_block{k}_impl", mesh=mesh)
        shards = transfers.shards("engine.decode")
    check(shards == 4, f"engine.decode readback gathered {shards} shard(s), "
                       "expected 4")
    leaves = jax.tree_util.tree_leaves(dec._device_params())
    check(all(_spans(a, 4) for a in leaves),
          "a serving parameter does not span four devices")
    split = sum(a.addressable_shards[0].data.shape != a.shape
                for a in leaves)
    check(split > 0, "no serving parameter is actually partitioned")
    cache = dec.init_cache(sizes.num_slots)
    ck = cache[dec.attn_names[0]]["k"]
    g = dec.kv_heads_per_row    # heads to a 128-lane cache row (2 at 12x64)
    want = (sizes.num_slots // 2, sizes.heads // g // 2, sizes.t_max,
            g * (sizes.d_model // sizes.heads))
    check(_spans(ck, 4) and ck.addressable_shards[0].data.shape == want,
          f"KV cache shard {ck.addressable_shards[0].data.shape} on "
          f"{len(ck.sharding.device_set)} device(s); expected {want} on 4")
    del cache, ck
    mosaic = _seam_mosaic(dec)
    say(f"multichip: 2x2 serving mesh ok — {len(leaves)} parameter arrays "
        f"on 4 devices ({split} partitioned), KV cache shard {want}, "
        f"engine.decode readback over {shards} shards; Mosaic call in "
        f"{mosaic}")
    if require_mosaic:
        check(mosaic.get("prefill_slots_impl__m2x2"),
              "mesh prefill lowered WITHOUT the short-T Pallas kernel")

    mesh4 = make_mesh(4)
    x, y = _train_batch(sizes)
    sh = NamedSharding(mesh4, P("data"))
    xd, yd = jax.device_put(x, sh), jax.device_put(y, sh)
    per = (sizes.train_batch // 4, sizes.train_t)
    check(_spans(xd, 4) and
          all(s.data.shape == per for s in xd.addressable_shards),
          f"batch is not split {per} over four devices")
    t0 = time.perf_counter()
    trainer = GraphDataParallelTrainer(net, mesh4)
    trainer.fit_batch(DataSet(xd, yd))
    loss = float(net.score_value)
    check(np.isfinite(loss), f"data-parallel step loss {loss}")
    check(all(_spans(a, 4) for a in jax.tree_util.tree_leaves(net.params)),
          "a trained parameter does not span four devices")
    dp_mosaic = has_mosaic_call(
        trainer._jit_step, net.params, net.updater_state, net.state,
        net._inputs_dict([xd]), net._labels_dict([yd]), None, None,
        net.iteration)
    say(f"multichip: data-parallel step over 4 devices ok, batch shard "
        f"{per}, loss {loss:.4f}, {time.perf_counter() - t0:.1f}s "
        f"(compile included); Mosaic call in the step: {dp_mosaic}")
    if require_mosaic:
        check(dp_mosaic, "data-parallel step lowered WITHOUT the short-T "
                         "Pallas kernel")
    mosaic["dp_train_step"] = dp_mosaic
    return {"serve_mesh": "2x2", "decode_readback_shards": shards,
            "dp_loss": loss, "mosaic": mosaic}


# --------------------------------------------------------------------- main
def _cache_entries(path: str) -> int:
    try:
        return sum(f.endswith("-cache") for f in os.listdir(path))
    except FileNotFoundError:
        return 0


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "absent"


def main() -> int:
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    try:
        return _run()
    finally:
        faulthandler.cancel_dump_traceback_later()


def _run() -> int:
    global _platform
    t_start = time.perf_counter()
    import jax

    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    cache_dir = configure_compilation_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    dev = jax.devices()[0]
    _platform = dev.platform
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    entries0 = _cache_entries(cache_dir)
    say(f"device: jax {jax.__version__} jaxlib {_version('jaxlib')} libtpu "
        f"{_version('libtpu')}; platform {device['platform']}, kind "
        f"{device['kind']}, count {device['count']}; compile cache "
        f"{cache_dir} ({entries0} entries; JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    if device["platform"] != "tpu":
        print(f"chip_smoke: jax found no TPU (platform "
              f"{device['platform']!r}); this check runs on the chip only",
              file=sys.stderr)
        return 1

    phases: Dict[str, float] = {}

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        phases[name] = round(time.perf_counter() - t0, 1)
        return out

    kernels = timed("kernels", phase_kernels, FULL, True)
    gc.collect()
    net = timed("build", build_net, FULL)
    mosaic = timed("serve", phase_serve, net, FULL, True)
    gc.collect()
    losses = timed("train", phase_train, net, FULL, True)
    multi = timed("multichip", phase_multichip, net, FULL, True)

    entries1 = _cache_entries(cache_dir)
    say(f"compile cache: {entries0} -> {entries1} entries, "
        f"{cache_events['hits']} programs reused from it, "
        f"{cache_events['misses']} compiled and written")
    print(json.dumps({
        "summary": "chip_smoke", "device": device,
        "seconds": {**phases,
                    "total": round(time.perf_counter() - t_start, 1)},
        "compile_cache": {"dir": cache_dir, "entries_before": entries0,
                          "entries_after": entries1, **cache_events},
        "kernel_rel_err": kernels, "mosaic": mosaic,
        "train_losses": [round(v, 4) for v in losses],
        "multichip": multi}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
