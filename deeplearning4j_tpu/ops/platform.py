"""Backend-dependent execution policy knobs.

The reference tunes its execution around cuDNN/workspace quirks
(MultiLayerNetwork.java:1011 workspace configs); the TPU analogs are XLA
buffer donation for the jitted train/decode steps (halves peak parameter
memory in the train step, lets the KV cache update in place) and the
persistent compilation cache that every process-owning entry point turns
on. Donation is ON on every backend; ``DL4J_TPU_DONATE=0`` turns it off
for an A/B.
"""

from __future__ import annotations

import os
from typing import Optional

#: the one place compiled programs persist when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: a fixed path inside the checkout. The directory is part of
#: what makes a cache reusable from one process (and one run) to the next,
#: so it is never derived from ``~``, a temporary name, a pid or a clock.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_cache_min_secs: Optional[float] = None    # None: not configured yet


def train_donate_argnums(default=(0, 1, 2)):
    """donate_argnums for jitted train/decode steps."""
    env = os.environ.get("DL4J_TPU_DONATE")
    if env is not None and env.lower() in ("0", "false", "no"):
        return ()
    return default


def configure_compilation_cache(min_compile_secs: float = 1.0) -> str:
    """Enable JAX's persistent (on-disk) compilation cache for this process
    and return the directory it uses. Called by the entry points that own a
    process (``chip_smoke.py``, ``bench.py``, the fleet worker, examples,
    the perf scripts); safe to call repeatedly.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and no
    directory is set in code; otherwise the cache lives in
    :data:`REPO_CACHE_DIR`.

    ``min_compile_secs``: programs compiling faster than this are NOT
    persisted (jax default 1.0). Callers whose fixed costs are dominated by
    sub-second helper-program compiles (the word2vec scan path: seven
    sub-second programs per process, BASELINE.md r4) pass 0.0 — scoped per
    caller rather than globally, so ordinary users don't accumulate
    unbounded tiny cache files. Repeated calls may only LOWER the floor."""
    global _cache_min_secs
    import jax
    if _cache_min_secs is None and \
            not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(REPO_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    if _cache_min_secs is None or min_compile_secs < _cache_min_secs:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_secs))
        _cache_min_secs = float(min_compile_secs)
    return jax.config.jax_compilation_cache_dir
