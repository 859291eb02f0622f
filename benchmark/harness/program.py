"""Kept for one caller outside the benchmark, ``scripts/perf_kernel_checks.py``
(which a benchmark PR may not edit; PERF.md, Open questions): the program of
a configuration, built by the configuration's own family."""

from __future__ import annotations

from typing import Dict

from . import manifest


def make_net(config: Dict):
    return manifest.Manifest().family(config).make_net(config)
