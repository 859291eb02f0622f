"""The slab attention reader (``readers/slab.py``): the share of the k/v slab
the decode steps' attention read, from the window's counters, as the
manifest finds it for ``gpt2-large.chat-open`` and for no other cell; None,
and no raise, where the program has no such counters (before they were
added) or held nothing."""

from __future__ import annotations

import types

import pytest

import tiny
from benchmark.harness import manifest as mf

METRIC = "slab_read_share.chat"


@pytest.fixture(scope="module")
def man():
    return mf.Manifest(tiny.ROOT)


def _ctx(stats):
    return types.SimpleNamespace(engine_stats=stats, trace=None)


def test_the_metric_is_listed_for_chat_open_alone(man):
    entry = [m for m in man.doc["per_layer"] if m["name"] == METRIC]
    assert len(entry) == 1 and entry[0]["workloads"] == \
        ["gpt2-large.chat-open"]
    assert entry[0]["layer"] == "kernels (kernels/slab_attention.py)"
    assert METRIC in [m["name"] for m in
                      man.per_layer("gpt2-large.chat-open")]
    assert callable(man.reader(METRIC))


@pytest.mark.parametrize("read,held,share", [
    (4864, 16384, 100.0 * 4864 / 16384),
    (16384, 16384, 100.0),
    (0, 16384, 0.0),
])
def test_the_share_is_read_over_held(man, read, held, share):
    got = man.reader(METRIC)(_ctx({"slab_positions_read": read * 36,
                                   "slab_positions_held": held * 36}))
    assert got == pytest.approx(share)


@pytest.mark.parametrize("stats", [
    None, {}, {"slab_positions_held": 10},
    {"slab_positions_read": 0, "slab_positions_held": 0},
], ids=["no-stats", "no-counters", "no-read", "nothing-held"])
def test_nothing_to_read_gives_none(man, stats):
    assert man.reader(METRIC)(_ctx(stats)) is None
