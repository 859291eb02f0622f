#!/usr/bin/env python
"""Bucketed device-time accounting for the transformer-LM step from the last
captured xplane trace (run scripts/perf_lm_profile.py first).

Buckets every synchronous "XLA Ops" event by what it touches — the vocab-side
CE/logits complex (any op reading/writing a [.., 32000] operand), attention
custom-calls, matmul fusions, adam/updater ops, layernorm/elementwise — and
prints us/step per bucket so BASELINE.md can carry the table.

--audit-compiles runs a DIFFERENT check that needs no trace: the bucketed
LM decode paths (models.generate's fixed-bucket recompute loop and the
KV-cache TransformerDecoder loop) execute under the runtime compile
auditor (analysis/compile_audit.py) and the per-function compile counts
are printed as JSON. The invariant gated here is the one the fixed
bucket exists for: steady-state decode is exactly ONE compile per shape
signature — a retrace per emitted token (seconds each) is the
failure mode this detects. Exit code 1 on any duplicate-
signature compile or on decode loops compiling more than once per
bucket. Shrink with BENCH_GEN_DMODEL/HEADS/LAYERS/VOCAB for CPU smoke.
"""
import collections
import glob
import json
import os
import re
import sys

STEPS = 5


def audit_compiles_report() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.analysis import CompileAudit
    from deeplearning4j_tpu.models import (TransformerDecoder, generate,
                                           transformer_lm_conf)
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    v = int(os.environ.get("BENCH_GEN_VOCAB", "256"))
    d = int(os.environ.get("BENCH_GEN_DMODEL", "64"))
    h = int(os.environ.get("BENCH_GEN_HEADS", "4"))
    nl = int(os.environ.get("BENCH_GEN_LAYERS", "2"))
    bucket = int(os.environ.get("BENCH_GEN_BUCKET", "64"))
    new_tokens = int(os.environ.get("BENCH_GEN_STEPS", "12"))
    conf = transformer_lm_conf(vocab_size=v, d_model=d, num_heads=h,
                               num_layers=nl, max_length=bucket)
    net = ComputationGraph(conf, compute_dtype=jnp.bfloat16).init()
    rng = np.random.default_rng(0)

    with CompileAudit() as audit:
        # fixed-bucket no-cache loop: MIXED prompt lengths must all reuse
        # the one [1, bucket] program (padding makes length invisible)
        for plen in (3, 7, 12):
            prompt = rng.integers(0, v, plen)
            generate(net, prompt, new_tokens, temperature=0.0,
                     bucket=bucket)
        # KV-cache decode loop: ONE decode_step_impl compile serves every
        # step and every later batch of the same shape
        dec = TransformerDecoder(net)
        prompts = [rng.integers(0, v, n) for n in (3, 7, 12, 5)]
        dec.generate(prompts, new_tokens, temperature=0.0)
        dec.generate([p[::-1].copy() for p in prompts], new_tokens,
                     temperature=0.0)     # same shapes -> zero new compiles

    report = audit.report()
    nocache_out_compiles = audit.compiles("_out")
    decode_compiles = audit.compiles("decode_step_impl")
    report["bucketed_nocache_output_compiles"] = nocache_out_compiles
    report["kv_decode_step_compiles"] = decode_compiles
    report["config"] = {"vocab": v, "d_model": d, "heads": h, "layers": nl,
                        "bucket": bucket, "new_tokens": new_tokens}
    # nocache_out_compiles is _out's FINAL total, read after the decode
    # phase too — == 1 also proves the decode loop re-compiled nothing
    ok = (report["duplicate_signature_compiles"] == 0 and
          nocache_out_compiles == 1 and decode_compiles == 1)
    report["ok"] = ok
    print(json.dumps(report, indent=1))
    return 0 if ok else 1


def xplane_report() -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    f = sorted(glob.glob('/tmp/jaxprof/**/*.xplane.pb', recursive=True))[-1]
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(open(f, 'rb').read())

    for plane in xs.planes:
        if 'TPU' not in plane.name:
            continue
        evmeta = plane.event_metadata
        buckets = collections.Counter()
        names = collections.defaultdict(collections.Counter)
        total = 0.0
        for line in plane.lines:
            if line.name != 'XLA Ops':
                continue
            for ev in line.events:
                name = evmeta[ev.metadata_id].name
                # classify on the op SYMBOL — substring tests over the full
                # text mis-bucketed every op whose operand list mentioned a
                # custom-call result (r5: 58.7 ms landed in 'custom-call')
                sym = name.split(' = ')[0]
                us = ev.duration_ps / 1e6
                total += us
                if '32000' in name:
                    b = 'vocab/CE complex'
                elif 'custom-call' in sym or sym.startswith('%run'):
                    # Pallas kernels lower to custom-calls named %run.N
                    b = 'custom-call (attention kernel / host)'
                elif 'copy' in sym:
                    b = 'copies'
                elif re.search(r'(convolution|dot)', sym):
                    b = 'matmul fusions'
                elif 'transpose' in sym:
                    b = 'transposes'
                elif 'divide_subtract' in sym or 'subtract_multiply' in sym:
                    b = 'updater'
                else:
                    b = 'other fusions/elementwise'
                buckets[b] += us
                names[b][re.sub(r'[.\d]+$', '', sym)] += us
        print(f'total sync device time: {total/STEPS/1000:.1f} ms/step')
        for b, us in buckets.most_common():
            print(f'  {b:42s} {us/STEPS/1000:8.2f} ms/step')
            for n, nus in names[b].most_common(10):
                print(f'      {n:50s} {nus/STEPS/1000:8.2f}')
    return 0


if __name__ == "__main__":
    from deeplearning4j_tpu.ops.platform import configure_compilation_cache
    configure_compilation_cache()
    if "--audit-compiles" in sys.argv[1:]:
        sys.exit(audit_compiles_report())
    sys.exit(xplane_report())
