"""Test harness: force an 8-device virtual CPU platform BEFORE jax imports,
so sharding/collective tests exercise real multi-device semantics without TPU
hardware (the pattern SURVEY.md §4 prescribes: local[n]-Spark analog)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"   # force-set: tests never take a chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")  # float64 for gradient checks

import jax

# Robust even if a pytest plugin imported jax before this conftest ran:
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# graftlint satellite (ISSUE 2): implicit rank promotion is a silent
# correctness hazard (a [B] vector broadcasting against [B, T] hides a
# missing axis); library code annotates every INTENDED mixed-rank
# broadcast explicitly ([None, :]-style), so tests run with promotion
# errors FATAL to keep it that way.
jax.config.update("jax_numpy_rank_promotion", "raise")

# The suite builds the same small programs over and over (every test makes
# its own nets and jits); with the persistent compile cache on from the
# first test, each distinct program compiles once per session — and not at
# all on a re-run (single-process tier-1 on this box: 648 -> 792 tests inside
# the 870 s limit, cold). Floor 0 s: almost every test program compiles in
# under a second. CompileAudit counts lowerings, which happen before the
# cache is consulted, so its accounts are unchanged.
from deeplearning4j_tpu.ops.platform import configure_compilation_cache

configure_compilation_cache(min_compile_secs=0.0)

import numpy as np
import pytest


@pytest.fixture
def rng_np():
    return np.random.default_rng(12345)
