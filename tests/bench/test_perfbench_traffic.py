"""The harness's own traffic generator: the same events as the
repository's synthetic source, counts recovered by binning, and the
arrival plans of both mixes."""
from __future__ import annotations

from dataclasses import replace

import jax
import numpy as np
import pytest

import perfbench_cells  # noqa: F401  (puts the repository on the path)

from bench.traffic import generator as g

FAMILIES = [("gesture", 16, 100.0), ("nmnist", 12, 60.0)]


def _spec(cfg) -> g.StreamSpec:
    return g.StreamSpec(family=cfg.name, height=cfg.height, width=cfg.width,
                        n_classes=cfg.n_classes, duration_ms=cfg.duration_ms,
                        contrast_gain=cfg.contrast_gain,
                        oversample=cfg.oversample, blob_sigma=cfg.blob_sigma,
                        seed_jitter=cfg.seed_jitter)


@pytest.mark.parametrize("family,hw,dur", FAMILIES)
def test_pool_replays_the_synthetic_source_event_for_event(family, hw, dur):
    from repro.data import events, sources

    cfg = (events.dvs_gesture_like(hw) if family == "gesture"
           else events.nmnist_like(hw))
    cfg = replace(cfg, duration_ms=dur)
    src = sources.SyntheticSource(cfg)
    key = jax.random.PRNGKey(2 ** 31 + 7)
    pool = g.make_pool(key, _spec(cfg), 2, slot_us=2500, chunk_us=2500)
    keys = g.stream_keys(key, 2)
    assert pool.labels.tolist() == [0, 1]
    for i in range(2):
        label, chunks = src.iter_event_chunks(keys[i], chunk_us=2500,
                                              slot_us=2500, label=i)
        want = list(chunks)
        assert label == i
        assert len(want) == len(pool.chunks[i])
        for a, b in zip(want, pool.chunks[i]):
            for f in "txyp":
                np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_events_bin_back_to_their_counts():
    from repro.data.binning import bin_chunks

    rng = np.random.default_rng(0)
    counts = rng.poisson(0.3, (8, 6, 5, 2)).astype(np.uint8)
    ev = g.counts_to_events(counts, 2500)
    chunks = g.chunk_events(ev, 5000, 4)
    frames = bin_chunks(chunks, n_total=8, slot_us=2500, sensor_hw=(6, 5),
                        out_hw=(6, 5))
    np.testing.assert_array_equal(frames, counts.astype(np.float32))
    assert sum(len(c) for c in chunks) == int(counts.sum())


def test_every_seed_draws_the_same_classes():
    spec = g.StreamSpec("nmnist", 8, 8, 10, 20.0, 18.0, 3, 0.08, True)
    pools = [g.make_pool(jax.random.PRNGKey(s), spec, 12, slot_us=2500,
                         chunk_us=2500) for s in (1, 2)]
    assert pools[0].labels.tolist() == pools[1].labels.tolist() \
        == [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 0, 1]
    assert not np.array_equal(pools[0].counts, pools[1].counts)


def test_replay_source_cycles_the_pool_and_labels_its_entries():
    spec = g.StreamSpec("gesture", 8, 8, 11, 40.0, 18.0, 3, 0.12, True)
    pool = g.make_pool(jax.random.PRNGKey(1), spec, 3, slot_us=2500,
                       chunk_us=2500)
    src = g.ReplaySource(pool)
    assert src.n_slots(10.0) == 4
    opened = [src.iter_event_chunks(None, chunk_us=2500) for _ in range(5)]
    assert [i for i, _ in opened] == [0, 1, 2, 0, 1]
    assert len(list(opened[3][1])) == 16
    short = g.ReplaySource(pool, duration_ms=20.0)
    assert len(list(short.iter_event_chunks(None, chunk_us=2500)[1])) == 8
    with pytest.raises(ValueError):
        src.iter_event_chunks(None, chunk_us=5000)


def test_plans_of_both_mixes():
    up = g.plan({"arrivals": "upfront", "paced": False}, capacity=8,
                duration_s=2.0, seconds=10.0, cohort_s=3.0)
    assert up == {"n_streams": 24, "paced": False, "offered_rate": None,
                  "max_pending": None}
    even = g.plan({"arrivals": "even", "paced": True, "load": 0.5,
                   "max_pending_per_lane": 1.0}, capacity=8, duration_s=2.0,
                  seconds=10.0, cohort_s=0.0)
    # one connection every 0.5 s for 8 s, then the last stream's 2 s
    assert even == {"n_streams": 17, "paced": True, "offered_rate": 2.0,
                    "max_pending": 8}
