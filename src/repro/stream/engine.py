"""Online event-stream serving with continuous batching, admission
control, and (optionally) paced real-time replay.

The missing half of the offline reproduction: the sweep engine measures
circuit variants in batch; this engine SERVES one deployed variant
(repro.stream.deploy) against many concurrent live event streams.

Lifecycle of one stream (see docs/streaming.md):

  1. the stream is OFFERED (all at once, or trickled at
     ``offered_rate`` streams/s on the replay clock) and enters the
     bounded pending queue — or is SHED when the queue is full
     (backpressure: offered load beyond ``capacity + max_pending`` is
     rejected, not buffered without bound);
  2. when a lane of the shared :class:`~repro.serve.slots.SlotManager`
     frees up at a T_INTG window boundary, the stream is ADMITTED: only
     now is its replay iterator opened
     (``EventSource.iter_event_chunks`` — AEDAT / N-MNIST file or the
     synthetic generator, replayed as timestamped raw ``(t, x, y, p)``
     chunks) and the lane's charge/membrane state zeroed (precharge) —
     resident iterators never exceed the lane capacity;
  3. at each window's start every host-side bin worker takes the
     window's replay chunks of the occupied lanes it owns and bins them
     onto the fine sub-slot grid (repro.data.binning semantics, sensor
     → model downscale included) in one vectorised pass, straight into
     its lane rows of one of two reused dense window batches; once
     every worker is done, ONE host → device copy and ONE jitted
     lane-batched ``fold`` advance every lane's leak ODE + conv deposit
     through all of the window's sub-slots together — no per-tick host
     sync; the window's only sync point is its readout;
  4. at each T_INTG boundary one jitted ``readout`` comparator-reads
     every lane, accumulates pooled spikes toward the backbone coarse
     grid, and — per lane, whenever ITS coarse window completes — steps
     the stateful spiking backbone and the rate-decoded logit average;
  5. after the stream's full duration the lane's prediction is
     finalized, the slot is released, and the pending queue refills it.

All lanes advance on one shared replay clock (micro-batching), but
admission/finalization are per-lane — classic continuous batching, the
same ``SlotManager`` contract the LM decode server uses.

**Paced mode** (``serve(..., paced=True)``) turns the replayer into a
real-time server: the scheduler holds window ``k`` until wall clock
``t_admit + k·t_intg`` and records a *deadline miss* whenever a readout
completes after its boundary ``t_admit + (k+1)·t_intg`` — in a physical
P²M sensor the passive capacitor's charge-retention bounds T_INTG, so a
late readout reads leaked charge; it is a correctness event, not just a
latency sample. Predictions are bit-identical to unpaced replay on the
same seed (pacing only inserts sleeps); per-lane and fleet-wide miss
counters plus the miss-margin histogram land in the
``p2m-stream-serving/v5`` stats artifact.

**Registry mode** (``StreamEngine(Registry(...))``,
repro.stream.registry) serves a CATALOG of circuit variants from one
lane table: streams request a variant at offer time, admission binds
each lane to a registry entry (rejecting unresolvable requests), and
``register``/``retire`` hot-swap entries mid-serve without perturbing
lanes bound to other entries. The v4 artifact adds the ``registry``
block (compat digest + per-entry admitted/finished/miss/throughput
rows) and ``admission.n_rejected``.

**Adaptation mode** (``StreamEngine(..., adapt=AdaptConfig(...))``,
repro.stream.adapt) turns on per-lane online plasticity: each lane
carries persistent weight/threshold deltas that a local
surrogate-gradient or reward-modulated rule updates at every labeled
coarse-window readout, compensating per-device leak drift in place.
The deltas ride in the engine's device lane state (``state["adapt"]``,
resident across ``serve`` calls), survive stream turnover on a lane,
reset when the lane rebinds to a different registry entry uid, and are
harvested via :meth:`StreamEngine.harvest` into validated delta
checkpoints (repro.stream.deploy.save_adapt_delta) that re-register as
new entries.
``adapt=None`` (the default) compiles none of this — frozen serving is
IEEE-bit-identical to the adaptation-less engine — and the v5 artifact
carries the ``adaptation`` block (rule, per-lane update counts and
delta norms, pre/post-accuracy split) either way.

Every mode calls the same two jitted steps once a window,
``fold(state, frames, active, *extra)`` and
``readout(state, active, coarse_mask, *extra)``; only the trailing
per-window arguments ``extra`` differ (repro.stream.accumulator).

**Sharded mode** (``StreamEngine(executor=LaneExecutor(devices=n))``,
CLI ``--devices``) maps the lane axis onto a 1-D ``"lane"`` device mesh
(repro.stream.shard): the capacity pads up to a device multiple, each
device folds/reads out its contiguous lane block under ``shard_map``,
and per-shard :class:`~repro.serve.slots.ShardedSlots` bookkeeping sits
behind the SAME single admission front — one bounded pending deque feeds
a lane freed on any shard. Host binning scales with it: ``bin_workers``
:class:`_BinWorker` threads each own a disjoint slice of the lane axis
(aligned with the mesh shards when ``bin_workers == devices``) and bin
their lanes in parallel, one job each per window — the multi-worker
attack on the host-bound saturation knee. Sharded
serving, any worker count, and ``prefetch=False`` (the bit-identical
inline oracle) all produce identical predictions and ledgers to the
``devices=1`` single-worker path (sharded logits to a few ulp).

**Profiling.** Each step of the serving loop opens a
``jax.profiler.TraceAnnotation`` named ``p2m.<step>``, tagged with the
window, chunk, stream and lane it serves and the counts of its work; a
profiler trace puts them on the device's clock (docs/streaming.md,
"Profiling").
"""
from __future__ import annotations

import math
import queue as queue_mod
import threading
import time
from collections import deque
from dataclasses import asdict, dataclass, field
from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.data.binning import event_cells, slot_us_for
from repro.data.formats import EventChunk
from repro.data.sources import EventSource
from repro.serve.slots import ShardedSlots
from repro.stream.accumulator import make_stream_fns, stack_entries
from repro.stream.adapt import AdaptConfig, lane_stats, make_adapt_fns
from repro.stream.deploy import Deployment
from repro.stream.registry import (Registry, RegistryEntry, compat_digest,
                                   compat_key)
from repro.stream.shard import LaneExecutor

STATS_SCHEMA = "p2m-stream-serving/v5"


class EntryTableFull(RuntimeError):
    """The engine's fixed-size per-entry param table has no reclaimable
    slot for a newly requested registry entry (every slot still has lanes
    bound to it). Admission REJECTS the stream; raise ``max_entries`` to
    co-serve more simultaneous variants."""


@dataclass
class StreamResult:
    """Per-stream serving outcome."""
    stream_id: int
    label: int
    prediction: int
    correct: bool
    n_events: int
    n_readouts: int
    n_coarse_frames: int
    offered_window: int       # global window tick the stream was offered
    admitted_window: int      # global window tick the stream was admitted
    finished_window: int
    n_misses: int = 0         # paced mode: readouts past their deadline
    # worst (largest) miss margin over the stream's readouts, ms;
    # negative = every readout beat its deadline; None = unpaced run
    miss_margin_max_ms: float | None = None
    # registry entry the lane was bound to at admission ("default" on a
    # single-deployment engine); uid disambiguates across hot-swaps
    entry: str = "default"
    entry_uid: int = 0
    # pooled layer-1 spikes the stream's sensor emitted over all readouts
    # (its share of total_layer1_spikes: the in-pixel layer's bandwidth)
    n_layer1_spikes: float = 0.0
    logits: list[float] = field(default_factory=list)  # rate-decoded mean


@dataclass
class _Lane:
    """Host-side state of one admitted stream."""
    stream_id: int
    label: int
    chunks: Iterator[EventChunk]
    n_windows: int
    offered_window: int = 0
    admitted_window: int = 0
    windows_done: int = 0
    n_events: int = 0
    n_layer1_spikes: float = 0.0
    t_cursor_us: int = 0
    n_misses: int = 0
    worst_margin_ms: float | None = None
    entry_name: str = "default"   # registry entry bound at admission
    entry_uid: int = 0
    entry_slot: int = 0           # engine param-table slot of that entry


class _BinWorker:
    """Single host-side worker thread binning replay chunks off the
    serving thread: one job a window, which bins the window's chunks of
    the worker's lanes straight into their rows of the window batch
    while the other workers bin theirs. Jobs are executed strictly in
    submission order — a lane's replay iterator is only ever advanced on
    the ONE worker that owns that lane, so chunk order per lane is
    preserved. Exceptions propagate to the consumer at ``get()``."""

    _STOP = object()

    def __init__(self, index: int = 0):
        self._tasks: queue_mod.Queue = queue_mod.Queue()
        self._results: queue_mod.Queue = queue_mod.Queue()
        self._thread = threading.Thread(
            target=self._run, name=f"stream-bin-worker-{index}",
            daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            job = self._tasks.get()
            if job is self._STOP:
                return
            try:
                self._results.put((job(), None))
            except BaseException as e:  # surfaced at get()
                self._results.put((None, e))

    def submit(self, job) -> None:
        self._tasks.put(job)

    def get(self):
        frames, err = self._results.get()
        if err is not None:
            raise err
        return frames

    def close(self) -> None:
        """Drain-and-join: cancel every not-yet-started job, stop the
        thread, and drop queued results. On the serve loop's exception
        path this releases the job closures' references to live replay
        iterators instead of leaking them to a parked daemon thread."""
        try:
            while True:
                self._tasks.get_nowait()
        except queue_mod.Empty:
            pass
        self._tasks.put(self._STOP)
        self._thread.join(timeout=10)
        try:
            while True:
                self._results.get_nowait()
        except queue_mod.Empty:
            pass

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()


class _BinPool:
    """Fixed pool of :class:`_BinWorker` threads, one per lane partition
    (the engine assigns each worker a contiguous slice of the lane axis —
    mesh-shard-aligned when ``bin_workers == devices``). The consumer
    submits one job per worker per window and gathers them in worker
    order; workers write disjoint lane rows of the window batch, so the
    folded frames are the same for any worker count."""

    def __init__(self, n: int):
        self.workers = [_BinWorker(i) for i in range(n)]

    def submit(self, worker: int, job) -> None:
        self.workers[worker].submit(job)

    def get(self, worker: int):
        return self.workers[worker].get()

    def close(self) -> None:
        for w in self.workers:
            w.close()

    @property
    def any_alive(self) -> bool:
        return any(w.alive for w in self.workers)


@dataclass
class ServingReport:
    """Everything one serve() run produced; ``to_artifact()`` is the
    serving-stats JSON the CLI emits and CI schema-checks."""
    results: list[StreamResult]
    deployed: dict
    capacity: int
    chunks_per_window: int
    t_intg_ms: float
    wall_s: float
    total_events: int
    total_readouts: int
    total_layer1_spikes: float
    paced: bool = False
    offered_rate: float | None = None
    max_pending: int | None = None
    devices: int = 1              # lane-mesh shards (1 = unsharded)
    bin_workers: int = 1          # host binning worker threads
    padded_capacity: int = 0      # lane axis after mesh padding
    lanes_per_shard: int = 0
    per_shard_admitted: list[int] = field(default_factory=list)
    n_offered: int = 0
    n_admitted: int = 0
    n_shed: int = 0               # rejected: pending queue was full
    # rejected at admission: variant request unresolvable (no match,
    # ambiguous, incompatible compat key, or entry table full)
    n_rejected: int = 0
    n_deferred: int = 0           # admitted later than their offer window
    # registry view: compat digest of the serving geometry, param-table
    # size, and one per-entry counter row per (name, uid) ever admitted
    registry_compat: str = ""
    registry_max_entries: int = 1
    entry_rows: list[dict] = field(default_factory=list)
    max_open_streams: int = 0     # peak concurrently-open replay iterators
    # adaptation view (None = engine served frozen): rule, cumulative
    # update count, per-lane delta rows, pre/post accuracy split
    adaptation: dict | None = None
    n_misses: int = 0             # fleet-wide deadline misses (paced)
    # one margin per (occupied lane, window) readout in paced mode:
    # readout completion − deadline, ms (positive = missed)
    miss_margin_ms: list[float] = field(default_factory=list)
    readout_s: list[float] = field(default_factory=list)
    fold_s: list[float] = field(default_factory=list)

    @property
    def accuracy(self) -> float:
        if not self.results:
            return 0.0
        return sum(r.correct for r in self.results) / len(self.results)

    @property
    def miss_rate(self) -> float:
        n = len(self.miss_margin_ms)
        return self.n_misses / n if n else 0.0

    def deadline_stats(self) -> dict:
        """Fleet-wide deadline accounting: counters, miss-margin
        percentiles, and a coarse margin histogram (empty on unpaced
        runs, where no readout carries a deadline)."""
        m = np.asarray(self.miss_margin_ms, dtype=float)
        if m.size:
            pct = {q: float(np.percentile(m, int(q[1:])))
                   for q in ("p50", "p90", "p99")}
            pct["max"] = float(m.max())
            counts, edges = np.histogram(m, bins=8)
            hist = {"edges_ms": [float(e) for e in edges],
                    "counts": [int(c) for c in counts]}
        else:
            pct = {"p50": 0.0, "p90": 0.0, "p99": 0.0, "max": 0.0}
            hist = {"edges_ms": [], "counts": []}
        return {"n_deadlines": int(m.size), "n_misses": self.n_misses,
                "miss_rate": self.miss_rate, "margin_ms": pct,
                "histogram": hist}

    def to_artifact(self) -> dict:
        lat = lambda xs, q: (float(np.percentile(xs, q) * 1e3)  # noqa: E731
                             if xs else 0.0)
        wall = max(self.wall_s, 1e-9)
        return {
            "schema": STATS_SCHEMA,
            "deployed": self.deployed,
            "n_streams": len(self.results),
            "capacity": self.capacity,
            "chunks_per_window": self.chunks_per_window,
            "t_intg_ms": self.t_intg_ms,
            "accuracy": self.accuracy,
            "paced": self.paced,
            "sharding": {
                "devices": self.devices,
                "bin_workers": self.bin_workers,
                "padded_capacity": self.padded_capacity,
                "lanes_per_shard": self.lanes_per_shard,
                "per_shard_admitted": list(self.per_shard_admitted),
            },
            "admission": {
                "offered_rate": self.offered_rate,
                "max_pending": self.max_pending,
                "n_offered": self.n_offered,
                "n_admitted": self.n_admitted,
                "n_shed": self.n_shed,
                "n_rejected": self.n_rejected,
                "n_deferred": self.n_deferred,
                "max_open_streams": self.max_open_streams,
            },
            "registry": {
                "compat": self.registry_compat,
                "max_entries": self.registry_max_entries,
                "entries": [
                    {**row,
                     "accuracy": (row["n_correct"] / row["n_finished"]
                                  if row["n_finished"] else 0.0),
                     "events_per_s": row["n_events"] / wall}
                    for row in self.entry_rows
                ],
            },
            "adaptation": (self.adaptation if self.adaptation is not None
                           else {"enabled": False, "rule": None,
                                 "lr_w": 0.0, "lr_theta": 0.0,
                                 "n_updates": 0, "accuracy_pre": None,
                                 "accuracy_post": None, "lanes": []}),
            "deadlines": self.deadline_stats(),
            "streams": [asdict(r) for r in self.results],
            "latency_ms": {
                "readout_p50": lat(self.readout_s, 50),
                "readout_p99": lat(self.readout_s, 99),
                "readout_mean": (float(np.mean(self.readout_s) * 1e3)
                                 if self.readout_s else 0.0),
                "fold_p50": lat(self.fold_s, 50),
                "fold_p99": lat(self.fold_s, 99),
            },
            "throughput": {
                "wall_s": self.wall_s,
                "events_per_s": self.total_events / wall,
                # the fleet-scale metric: what ONE device of the lane
                # mesh sustains (events_per_s / devices)
                "events_per_s_per_device": (self.total_events / wall
                                            / max(self.devices, 1)),
                "readouts_per_s": self.total_readouts / wall,
                "streams_per_s": len(self.results) / wall,
                "layer1_spikes_per_s": self.total_layer1_spikes / wall,
            },
        }


class StreamEngine:
    """Continuous-batching online inference over one deployment — or,
    given a :class:`~repro.stream.registry.Registry`, over a CATALOG of
    compat-equal deployments with per-stream variant selection.

    **Registry mode** (``StreamEngine(registry, ...)``): the first
    registered entry anchors the shared serving geometry (compat key);
    per-lane numerics live in a fixed-size param table of ``max_entries``
    slots whose stacked bundle is an *argument* of the jitted
    fold/readout (``make_stream_fns(..., registry=True)`` in
    repro.stream.accumulator) — so ``register``/``retire`` on the live
    registry (hot-swap) re-stacks the bundle without recompiling and
    without perturbing lanes bound to other entries. Admission resolves
    each stream's variant request (``serve(..., variants=...)``) against
    the registry (:meth:`Registry.resolve`); unresolvable requests (no
    match / ambiguous / wrong compat / table full) REJECT the stream
    (``n_rejected``) instead of guessing. A retired entry's params stay
    in their table slot until the last lane bound to it releases, so
    in-flight streams finish on the exact weights they were admitted
    with. Mixed-variant serving is bit-identical per stream to
    single-variant serving (tests/test_registry.py).

    ``capacity`` is the fixed lane count of the jitted steps (the decode
    batch of LM serving); ``chunks_per_window`` sets the replay and
    binning granularity — how many raw-event chunks arrive, and are
    binned, per T_INTG window (must divide ``n_sub``; default: one
    chunk per fine sub-slot, the finest arrival granularity the binned
    contract expresses). The device sees whole windows whatever it is:
    the chunks are binned into one ``[capacity, n_sub, H, W, 2]``
    batch, copied once and folded by one call per window.
    ``use_kernel=True`` folds the window's sub-slots through the fused
    Pallas stream_fold kernel instead of the XLA scan (identical spike
    maps and predictions, charge to a few ulp — tests/test_stream_fold.py
    pins it). ``prefetch=False`` turns
    off the async host-binning workers and runs their window jobs inline
    on the serving thread (debug aid; the folded numbers are identical).

    ``executor`` (repro.stream.shard.LaneExecutor) shards the lane axis
    over a 1-D ``"lane"`` device mesh: the capacity pads up to a multiple
    of ``executor.devices`` (padding lanes are never admitted) and the
    jitted steps run under ``shard_map`` — identical predictions, ledgers
    and spike counts to the default single-device executor, logits to a
    few ulp. ``bin_workers`` sets the host binning
    pool width (default: one worker per mesh shard, so ``devices=1``
    keeps the single-worker pipeline); each worker owns a fixed disjoint
    slice of the lane axis, which keeps per-lane chunk order — and the
    binned frames — deterministic for any worker count.
    """

    def __init__(self, dep: "Deployment | Registry", *, capacity: int = 4,
                 chunks_per_window: int | None = None,
                 use_kernel: bool = False, prefetch: bool = True,
                 executor: LaneExecutor | None = None,
                 bin_workers: int | None = None,
                 max_entries: int | None = None,
                 default_entry: str | None = None,
                 adapt: AdaptConfig | None = None):
        if isinstance(dep, Registry):
            if len(dep) == 0:
                raise ValueError(
                    "registry is empty — register at least one entry "
                    "before building a serving engine")
            self.registry: Registry | None = dep
            anchor = next(dep.entries())
            self.compat = anchor.compat
            self.dep = anchor.dep
            self.default_entry = default_entry
            self.max_entries = (max(len(dep) + 1, 2)
                                if max_entries is None else max_entries)
            if self.max_entries < len(dep):
                raise ValueError(
                    f"max_entries={self.max_entries} cannot hold the "
                    f"{len(dep)} already-registered entries")
        else:
            if max_entries is not None or default_entry is not None:
                raise ValueError("max_entries/default_entry require a "
                                 "registry-backed engine")
            self.registry = None
            self.dep = dep
            self.compat = compat_key(dep)
            self.default_entry = None
            self.max_entries = 1
        cfg = self.dep.model_cfg.p2m
        dep = self.dep
        self.capacity = capacity
        self.executor = executor or LaneExecutor()
        self.padded_capacity = self.executor.padded_size(capacity)
        self.lanes_per_shard = self.padded_capacity // self.executor.devices
        if bin_workers is not None and bin_workers < 1:
            raise ValueError(f"bin_workers must be >= 1, got {bin_workers}")
        self.bin_workers = (self.executor.devices if bin_workers is None
                            else bin_workers)
        self.n_sub = cfg.n_sub
        self.chunks_per_window = (self.n_sub if chunks_per_window is None
                                  else chunks_per_window)
        if self.n_sub % self.chunks_per_window:
            raise ValueError(
                f"chunks_per_window={self.chunks_per_window} must divide "
                f"n_sub={self.n_sub}")
        self.chunk_slots = self.n_sub // self.chunks_per_window
        self.slot_us = slot_us_for(cfg.t_intg_ms, cfg.n_sub)
        self.chunk_us = self.slot_us * self.chunk_slots
        self.group = dep.model_cfg.coarsen_group()
        self.backbone_kind = dep.model_cfg.backbone.kind
        self.use_kernel = use_kernel
        self.prefetch = prefetch
        self.adapt = adapt
        build = (make_stream_fns if adapt is None
                 else partial(make_adapt_fns, adapt=adapt))
        self.fns = build(dep, capacity=self.padded_capacity,
                         use_kernel=use_kernel, executor=self.executor,
                         registry=self.registry is not None)
        # the device lane state, resident across serve() calls: a lane
        # keeps its learned adaptation deltas over stream turnover and
        # harvest works after the run; admission resets each lane
        self.state = self.fns.init_state()
        if adapt is not None:
            # entry uid each lane's deltas were learned against (-1 =
            # never admitted): rebinding to a different uid voids them
            self._lane_entry_uid = np.full((self.padded_capacity,), -1,
                                           np.int64)
            self._lane_base: list[Deployment | None] = \
                [None] * self.padded_capacity
            self._lane_base_name = ["default"] * self.padded_capacity
            self._labels = np.full((self.padded_capacity,), -1, np.int32)
        if self.registry is not None:
            # fixed-size per-entry param table: slot i holds the numerics
            # of one (name, uid) registration; refcounts track how many
            # resident lanes are bound to it, so hot-swap keeps a retired
            # entry's weights until its last lane drains. Unused slots
            # hold the anchor's numerics as shape placeholders.
            anchor_nb = self.fns.entry_numerics(dep)
            self._entry_slots: list[tuple[str, int] | None] = \
                [None] * self.max_entries
            self._entry_refs = [0] * self.max_entries
            self._entry_nbs = [anchor_nb] * self.max_entries
            self._bundle = stack_entries(self._entry_nbs)
            self._entry_of = np.zeros((self.padded_capacity,), np.int32)

    # -- registry param-table bookkeeping ------------------------------
    def _slot_stale(self, slot: int) -> bool:
        """True when the table slot's (name, uid) is no longer live in
        the registry (retired, or the name was hot-swapped to a new
        uid) — reclaimable once its refcount hits zero."""
        assert self.registry is not None
        key = self._entry_slots[slot]
        if key is None:
            return True
        name, uid = key
        return name not in self.registry or self.registry.get(name).uid != uid

    def _bind_entry(self, entry: RegistryEntry) -> int:
        """Bind one more lane to ``entry``, installing its numerics into
        the param table on first use (re-stacking the device bundle —
        shapes unchanged, so no recompile). Raises :class:`EntryTableFull`
        when every slot still has lanes bound to it."""
        key = (entry.name, entry.uid)
        for i, k in enumerate(self._entry_slots):
            if k == key:
                self._entry_refs[i] += 1
                return i
        victim = None
        for i in range(self.max_entries):
            if self._entry_refs[i] == 0 and self._slot_stale(i):
                victim = i
                break
        if victim is None:  # evict a live-but-unused cached entry
            for i in range(self.max_entries):
                if self._entry_refs[i] == 0:
                    victim = i
                    break
        if victim is None:
            raise EntryTableFull(
                f"all {self.max_entries} entry slots have resident lanes "
                f"(bound: {[k for k in self._entry_slots if k]}) — raise "
                f"max_entries to co-serve more variants")
        self._entry_slots[victim] = key
        self._entry_nbs[victim] = self.fns.entry_numerics(entry.dep)
        self._entry_refs[victim] = 1
        self._bundle = stack_entries(self._entry_nbs)
        return victim

    def _unbind_entry(self, slot: int) -> None:
        assert self._entry_refs[slot] > 0
        self._entry_refs[slot] -= 1

    def _window_extra(self) -> tuple:
        """The serving mode's trailing step arguments for one window:
        the registry's per-lane entry indices and (possibly just
        re-stacked) param bundle — same shapes, no recompile — then
        adaptation's per-lane stream labels."""
        extra = (() if self.registry is None else
                 (jnp.asarray(self._entry_of), self._bundle))
        if self.adapt is not None:
            extra += (jnp.asarray(self._labels),)
        return extra

    # ------------------------------------------------------------------
    def open_stream(self, source: EventSource, key: jax.Array,
                    stream_id: int) -> _Lane:
        """Open one replayed sample into an admission-ready lane record.

        Called at ADMISSION time, not at offer time: an open lane holds a
        live replay iterator (and, for file-backed sources, its buffers),
        so opening lazily bounds resident iterators by the lane capacity
        instead of the offered stream count. Admission time itself is
        stamped by ``serve`` when the lane is placed."""
        h, w = self.fns.in_hw
        if (source.height, source.width) != (h, w):
            raise ValueError(
                f"source resolution {(source.height, source.width)} does "
                f"not match the deployed model's input {(h, w)}")
        if source.n_classes > self.fns.n_classes:
            raise ValueError(
                f"source has {source.n_classes} classes but the deployed "
                f"head predicts {self.fns.n_classes} — labels past the "
                f"head are unservable")
        n_windows = source.n_slots(self.dep.t_intg_ms)
        if n_windows % self.group:
            raise ValueError(
                f"stream duration {source.duration_ms:g} ms yields "
                f"{n_windows} T_INTG windows, not a multiple of the "
                f"deployed coarse group {self.group} "
                f"(coarse_window_ms={self.dep.model_cfg.coarse_window_ms:g})"
                f" — the backbone would never step; deploy a record whose "
                f"coarse window fits the stream")
        label, chunks = source.iter_event_chunks(
            key, chunk_us=self.chunk_us, slot_us=self.slot_us)
        return _Lane(stream_id=stream_id, label=label, chunks=chunks,
                     n_windows=n_windows)

    def _worker_of(self, lane: int) -> int:
        """Owning bin worker of a global lane: contiguous balanced slices
        of the padded lane axis, exactly shard-aligned when
        ``bin_workers == devices`` (worker w bins mesh shard w). A lane
        is owned by ONE worker for its whole lifetime, so its replay
        iterator only ever advances on that worker's thread."""
        return lane * self.bin_workers // self.padded_capacity

    def _partition(self, occupied: list[tuple[int, _Lane]]
                   ) -> list[list[tuple[int, _Lane]]]:
        """Split the occupied lanes by owning bin worker."""
        parts: list[list[tuple[int, _Lane]]] = [
            [] for _ in range(self.bin_workers)]
        for lane_i, lane in occupied:
            parts[self._worker_of(lane_i)].append((lane_i, lane))
        return parts

    def _window_frames(self) -> np.ndarray:
        """A zeroed host batch for one window's fold:
        [padded_capacity, n_sub, H, W, 2] (unoccupied and mesh-padding
        lanes stay zero; they fold masked-inactive)."""
        h, w = self.fns.in_hw
        return np.zeros((self.padded_capacity, self.n_sub, h, w, 2),
                        np.float32)

    def _bin_window(self, source: EventSource,
                    lanes: list[tuple[int, _Lane]], frames: np.ndarray,
                    stale: np.ndarray, window: int, worker: int
                    ) -> np.ndarray:
        """One worker's share of a window: the next ``chunks_per_window``
        replay chunks of each of its occupied ``lanes``, binned in one
        pass straight into their rows of the window batch ``frames``
        (offline-binner semantics: each chunk on its own sub-slot range,
        same slot grid, same sensor → model downscale). First zeroes
        ``stale``, the cells the worker wrote into ``frames`` at its last
        use, so a freed lane reads zero and a re-admitted one only its new
        stream. Returns the flat indices of the cells it wrote. Runs on
        the owning :class:`_BinWorker` thread when prefetching."""
        h, w = self.fns.in_hw
        chunk_cells = self.chunk_slots * h * w * 2
        lane_cells = self.n_sub * h * w * 2
        flat = frames.reshape(-1)
        with TraceAnnotation("p2m.bin", window=window, worker=worker,
                             lanes=len(lanes)) as span:
            flat[stale] = 0.0
            chunks, t0s, bases = [], [], []
            for lane_i, lane in lanes:
                for c in range(self.chunks_per_window):
                    chunk = next(lane.chunks)
                    lane.n_events += len(chunk)
                    chunks.append(chunk)
                    t0s.append(lane.t_cursor_us)
                    bases.append(lane_i * lane_cells + c * chunk_cells)
                    lane.t_cursor_us += self.chunk_us
            sizes = [len(c) for c in chunks]
            cells = np.zeros(0, np.int64)
            if sum(sizes):
                t, x, y, p = (np.concatenate([getattr(c, f) for c in chunks])
                              for f in ("t", "x", "y", "p"))
                ok, cells = event_cells(
                    t, x, y, p, t0_us=np.repeat(t0s, sizes),
                    n_slots=self.chunk_slots, slot_us=self.slot_us,
                    sensor_hw=source.sensor_hw, out_hw=(h, w))
                cells += np.repeat(bases, sizes)[ok]
                # counted by sort, not np.add.at: each cell once, so one
                # plain assignment writes the window's counts
                cells, counts = np.unique(cells, return_counts=True)
                flat[cells] = counts
            span.set_metadata(events=sum(sizes), cells=len(cells),
                              cleared=len(stale))
        return cells

    # ------------------------------------------------------------------
    def serve(self, source: EventSource, n_streams: int, *, seed: int = 0,
              paced: bool = False, offered_rate: float | None = None,
              max_pending: int | None = None, variants=None,
              on_window=None, log=None) -> ServingReport:
        """Serve ``n_streams`` replayed samples of ``source`` and return
        the serving report.

        ``offered_rate`` trickles the offers at that many streams/s on
        the replay clock (window ``w`` ↔ ``w·t_intg`` of stream time;
        under ``paced=True`` that is wall time too); default offers all
        streams up front. ``max_pending`` bounds the pending queue:
        offers arriving when ``pending + free lanes`` is exhausted are
        SHED and counted (``None`` = unbounded, no shedding). Offers,
        admission, and shedding are all driven by the deterministic
        window counter — never by the wall clock — so paced and unpaced
        runs of the same seed serve identical streams with bit-identical
        predictions; pacing only decides *when* each window runs and
        whether its readout missed its deadline.

        ``variants`` (registry mode) carries each stream's variant
        request — an entry name, a metadata matcher dict, or ``None``
        for the engine's ``default_entry`` — as a sequence of length
        ``n_streams`` or a callable ``stream_id -> request``, resolved
        at ADMISSION time against the live registry (so a hot-swap
        between offer and admission is honoured); unresolvable requests
        reject the stream (``n_rejected``). ``on_window(window)`` is
        called at the top of every window iteration — the hook tests and
        ops use to ``register``/``retire`` registry entries mid-serve
        (hot-swap) on the serving thread."""
        if offered_rate is not None and offered_rate <= 0:
            raise ValueError(f"offered_rate must be > 0 streams/s, got "
                             f"{offered_rate}")
        if max_pending is not None and max_pending < 0:
            raise ValueError(f"max_pending must be >= 0, got {max_pending}")
        if variants is None:
            req_of = lambda sid: None                         # noqa: E731
        elif self.registry is None:
            raise ValueError("variants requires a registry-backed engine")
        elif callable(variants):
            req_of = variants
        else:
            vlist = list(variants)
            if len(vlist) != n_streams:
                raise ValueError(f"variants has {len(vlist)} requests for "
                                 f"n_streams={n_streams}")
            req_of = lambda sid: vlist[sid]                   # noqa: E731
        key = jax.random.PRNGKey(seed)
        t_intg_s = self.dep.t_intg_ms * 1e-3
        offers_per_window = (None if offered_rate is None
                             else offered_rate * t_intg_s)

        def offer_window(i: int) -> int:
            return (0 if offers_per_window is None
                    else int(math.floor(i / offers_per_window)))

        slots: ShardedSlots[_Lane] = ShardedSlots(self.capacity,
                                                  self.executor.devices)
        pending: deque[tuple[int, int]] = deque()  # (stream_id, offered_w)
        state = self.state
        results: list[StreamResult] = []
        report = ServingReport(
            results=results, deployed=self.dep.deployed_meta(),
            capacity=self.capacity,
            chunks_per_window=self.chunks_per_window,
            t_intg_ms=self.dep.t_intg_ms, wall_s=0.0, total_events=0,
            total_readouts=0, total_layer1_spikes=0.0, paced=paced,
            offered_rate=offered_rate, max_pending=max_pending,
            devices=self.executor.devices, bin_workers=self.bin_workers,
            padded_capacity=self.padded_capacity,
            lanes_per_shard=self.lanes_per_shard,
            per_shard_admitted=[0] * self.executor.devices,
            registry_compat=compat_digest(self.compat),
            registry_max_entries=self.max_entries)
        # per-(name, uid) counter rows, created at first admission; the
        # dicts are shared with report.entry_rows and mutated in place
        rows: dict[tuple[str, int], dict] = {}

        def row_of(lane: _Lane) -> dict:
            k = (lane.entry_name, lane.entry_uid)
            if k not in rows:
                rows[k] = {"name": k[0], "uid": k[1], "n_admitted": 0,
                           "n_finished": 0, "n_correct": 0, "n_misses": 0,
                           "n_events": 0, "n_readouts": 0}
                report.entry_rows.append(rows[k])
            return rows[k]

        # warmup: compile fold/readout with every lane masked off, which
        # leaves the state's values as they are, so the latency
        # percentiles measure steady-state serving, not jit; the fold
        # compiles at the one shape serving dispatches, a window
        wx = self._window_extra()
        wmask = jnp.zeros((self.padded_capacity,), bool)
        wframes = jnp.asarray(self._window_frames())
        # the two window batches, written in turn, and per batch and bin
        # worker the flat indices of the cells it last wrote there
        batches = [self._window_frames() for _ in range(2)]
        written = [[np.zeros(0, np.int64)] * self.bin_workers
                   for _ in range(2)]
        ws = self.fns.fold(state, wframes, wmask, *wx)
        ws, _ = self.fns.readout(ws, wmask, wmask, *wx)
        jax.block_until_ready(ws["logits"])
        pool = _BinPool(self.bin_workers) if self.prefetch else None
        next_offer = 0
        window = 0
        t_start = time.perf_counter()
        try:
            while (next_offer < n_streams or pending
                   or not slots.is_empty()):
                # ---- ops hook (hot-swap point): runs before this
                # window's admissions so a swap at window k governs
                # every stream admitted at k onward; outside every span,
                # since a harness may open its own there ----------------
                if on_window is not None:
                    on_window(window)
                with TraceAnnotation("p2m.schedule", window=window) as span:
                    n_admitted, n_shed = report.n_admitted, report.n_shed
                    # ---- offers arriving at this window boundary ------
                    while (next_offer < n_streams
                           and offer_window(next_offer) <= window):
                        report.n_offered += 1
                        if (max_pending is not None
                                and len(pending) >= max_pending + slots.n_free):
                            report.n_shed += 1
                            if log is not None:
                                log(f"[admission] shed stream {next_offer} at "
                                    f"window {window} (pending full)")
                        else:
                            pending.append((next_offer, window))
                        next_offer += 1
                    # ---- lazy admission into free lanes (window boundary)
                    while pending and not slots.is_full():
                        sid, offered_w = pending.popleft()
                        with TraceAnnotation("p2m.admit", window=window,
                                             stream=sid) as admit:
                            if self.registry is not None:
                                # variant selection: resolve the stream's
                                # request against the LIVE registry;
                                # unresolvable → reject (never guess a
                                # variant for a sensor)
                                try:
                                    entry = self.registry.resolve(
                                        req_of(sid), compat=self.compat,
                                        default=self.default_entry)
                                    slot_e = self._bind_entry(entry)
                                except (LookupError, ValueError, TypeError,
                                        EntryTableFull) as e:
                                    report.n_rejected += 1
                                    admit.set_metadata(lane=-1)
                                    if log is not None:
                                        log(f"[admission] rejected stream "
                                            f"{sid} at window {window}: {e}")
                                    continue
                            lane = self.open_stream(
                                source, jax.random.fold_in(key, sid), sid)
                            lane.offered_window = offered_w
                            lane.admitted_window = window
                            if window > offered_w:
                                report.n_deferred += 1
                            lane_i = slots.admit(lane)
                            assert lane_i is not None
                            admit.set_metadata(lane=lane_i)
                            if self.registry is not None:
                                lane.entry_name = entry.name
                                lane.entry_uid = entry.uid
                                lane.entry_slot = slot_e
                                self._entry_of[lane_i] = slot_e
                            reset = self.fns.reset_lane
                            if self.adapt is not None:
                                # learned deltas persist across streams on
                                # the lane (it models one physical sensor)
                                # but are void against a different base
                                # entry
                                uid = (entry.uid if self.registry is not None
                                       else 0)
                                if self._lane_entry_uid[lane_i] != uid:
                                    reset = self.fns.reset_lane_full
                                self._lane_entry_uid[lane_i] = uid
                                self._lane_base[lane_i] = (
                                    entry.dep if self.registry is not None
                                    else self.dep)
                                self._lane_base_name[lane_i] = lane.entry_name
                                self._labels[lane_i] = lane.label
                            state = reset(state, lane_i)
                            report.n_admitted += 1
                            row_of(lane)["n_admitted"] += 1
                            report.per_shard_admitted[
                                slots.shard_of(lane_i)] += 1
                    report.max_open_streams = max(report.max_open_streams,
                                                  slots.n_occupied)
                    occupied = list(slots.occupied())
                    active = jnp.asarray(slots.active_mask())
                    extra = self._window_extra()
                    span.set_metadata(n_admitted=report.n_admitted - n_admitted,
                                      n_shed=report.n_shed - n_shed)
                # ---- paced: hold until this window's wall-clock start -
                if paced:
                    delay = (t_start + window * t_intg_s
                             - time.perf_counter())
                    with TraceAnnotation("p2m.pace", window=window,
                                         late_ms=max(0.0, -delay) * 1e3):
                        if delay > 0:
                            time.sleep(delay)
                # ---- bin and fold the window -------------------------
                # one job per bin worker, each on its own lane slice, in
                # parallel, straight into this window's batch; the batch
                # then goes to the device in one copy and one fold
                # dispatch, left in flight — the window's only host↔device
                # sync is the readout below. Batch window % 2 is written
                # again at window + 2, after window + 1's readout sync:
                # that sync waits on fold window + 1, whose state is fold
                # window's, the last reader of this batch (its copy
                # included, even where jnp.asarray aliases the buffer, as
                # it may on the CPU)
                frames = batches[window % 2]
                stale = written[window % 2]
                jobs = [partial(self._bin_window, source, lanes, frames,
                                stale[wi], window, wi)
                        for wi, lanes in enumerate(self._partition(occupied))]
                t0 = time.perf_counter()
                if pool is not None:
                    for wi, job in enumerate(jobs):
                        pool.submit(wi, job)
                with TraceAnnotation("p2m.bin_wait", window=window):
                    stale[:] = ([pool.get(wi)
                                 for wi in range(self.bin_workers)]
                                if pool is not None else
                                [job() for job in jobs])
                with TraceAnnotation(
                        "p2m.h2d", window=window,
                        chunks=self.chunks_per_window,
                        slots=self.n_sub, bytes=frames.nbytes):
                    frames_dev = jnp.asarray(frames)
                with TraceAnnotation(
                        "p2m.fold", window=window,
                        chunks=self.chunks_per_window,
                        slots=self.n_sub):
                    state = self.fns.fold(state, frames_dev, active, *extra)
                # one entry per replay chunk, each an equal share of the
                # window's feed: the bin wait, the copy, the fold dispatch
                report.fold_s.extend(
                    [(time.perf_counter() - t0) / self.chunks_per_window]
                    * self.chunks_per_window)
                # ---- readout at the T_INTG boundary -------------------
                coarse_mask = np.zeros((self.padded_capacity,), bool)
                for lane_i, lane in occupied:
                    coarse_mask[lane_i] = \
                        (lane.windows_done + 1) % self.group == 0
                t0 = time.perf_counter()
                # the program steps the backbone on every lane; the model
                # needs it only on the coarse_lanes at a coarse boundary
                with TraceAnnotation("p2m.readout", window=window,
                                     backbone=self.backbone_kind,
                                     coarse_lanes=int(coarse_mask.sum())):
                    state, out = self.fns.readout(
                        state, active, jnp.asarray(coarse_mask), *extra)
                with TraceAnnotation("p2m.sync", window=window):
                    n_spikes = np.asarray(out["n_spikes"])  # window sync
                t_done = time.perf_counter()
                report.readout_s.append(t_done - t0)
                # paced: every occupied lane's readout k carries deadline
                # t_admit + k·t_intg; on the shared replay clock that is
                # the window boundary t_start + (window+1)·t_intg
                margin_ms = ((t_done - (t_start + (window + 1) * t_intg_s))
                             * 1e3 if paced else None)
                window += 1
                for lane_i, lane in occupied:
                    lane.windows_done += 1
                    report.total_readouts += 1
                    row = row_of(lane)
                    row["n_readouts"] += 1
                    lane.n_layer1_spikes += float(n_spikes[lane_i])
                    report.total_layer1_spikes += float(n_spikes[lane_i])
                    if margin_ms is not None:
                        report.miss_margin_ms.append(margin_ms)
                        lane.worst_margin_ms = (
                            margin_ms if lane.worst_margin_ms is None
                            else max(lane.worst_margin_ms, margin_ms))
                        if margin_ms > 0:
                            lane.n_misses += 1
                            report.n_misses += 1
                            row["n_misses"] += 1
                    if lane.windows_done < lane.n_windows:
                        continue
                    # stream complete: finalize rate-decoded prediction
                    # (tagged with the window whose readout finished it)
                    with TraceAnnotation("p2m.finalise", window=window - 1,
                                         stream=lane.stream_id, lane=lane_i):
                        n_c = int(state["n_coarse"][lane_i])
                        logits = (np.asarray(state["logits"][lane_i])
                                  / max(n_c, 1))
                        pred = int(np.argmax(logits))
                        report.total_events += lane.n_events
                        row["n_finished"] += 1
                        row["n_correct"] += int(pred == lane.label)
                        row["n_events"] += lane.n_events
                        results.append(StreamResult(
                            stream_id=lane.stream_id, label=lane.label,
                            prediction=pred, correct=pred == lane.label,
                            n_events=lane.n_events,
                            n_readouts=lane.windows_done,
                            n_coarse_frames=n_c,
                            offered_window=lane.offered_window,
                            admitted_window=lane.admitted_window,
                            finished_window=window,
                            n_misses=lane.n_misses,
                            miss_margin_max_ms=lane.worst_margin_ms,
                            entry=lane.entry_name, entry_uid=lane.entry_uid,
                            n_layer1_spikes=lane.n_layer1_spikes,
                            logits=[float(v) for v in logits]))
                        slots.release(lane_i)
                        if self.registry is not None:
                            self._unbind_entry(lane.entry_slot)
                    if log is not None:
                        log(f"[stream {lane.stream_id}] label={lane.label} "
                            f"pred={pred} readouts={lane.windows_done} "
                            f"events={lane.n_events}"
                            + (f" misses={lane.n_misses}" if paced else ""))
        finally:
            # runs on the exception path too: a failed readout/fold must
            # drain-and-join every bin worker (cancelling queued jobs) so
            # no daemon thread leaks holding an open stream iterator
            if pool is not None:
                pool.close()
            self.state = state
        report.wall_s = time.perf_counter() - t_start
        if self.adapt is not None:
            lanes = lane_stats(jax.device_get(self.state["adapt"]))

            def _acc(rs: list[StreamResult]) -> float | None:
                return (sum(r.correct for r in rs) / len(rs)
                        if rs else None)

            # learning-curve split in finish order: accuracy over the
            # first vs second half of this run's streams — a cheap
            # online signal that adaptation is helping (tools/
            # ab_compare.py does the significance test properly)
            half = len(results) // 2
            report.adaptation = {
                "enabled": True,
                "rule": self.adapt.rule,
                "lr_w": self.adapt.lr_w,
                "lr_theta": self.adapt.lr_theta,
                "n_updates": sum(r["n_updates"] for r in lanes),
                "accuracy_pre": _acc(results[:half]),
                "accuracy_post": _acc(results[half:]),
                "lanes": lanes,
            }
        return report

    # ------------------------------------------------------------------
    def harvest(self, lane: int) -> dict:
        """One adapted lane's learned deltas + base identity, ready for
        delta-checkpoint export (repro.stream.deploy.save_adapt_delta)
        and re-registration as a new registry entry.

        The deltas are relative to the lane's base entry's QUANTIZED
        layer-1 weights and deployed threshold — exactly how the lane
        served them (``quantize(w_base + dw)``, ``theta_base + dtheta``).
        Harvesting a lane that never applied an update is allowed (zero
        deltas round-trip fine); a lane that never served raises."""
        if self.adapt is None:
            raise ValueError("engine was built without adapt= — nothing "
                             "to harvest")
        if not 0 <= lane < self.padded_capacity:
            raise ValueError(f"lane {lane} out of range "
                             f"[0, {self.padded_capacity})")
        base = self._lane_base[lane]
        if base is None:
            raise ValueError(f"lane {lane} never served a stream — no "
                             f"base entry to delta against")
        ast = jax.device_get(self.state["adapt"])
        return {
            "lane": lane,
            "dw": np.asarray(ast["dw"][lane]),
            "dtheta": float(ast["dtheta"][lane]),
            "n_updates": int(ast["n_updates"][lane]),
            "base_name": self._lane_base_name[lane],
            "base_uid": int(self._lane_entry_uid[lane]),
            "base": base,
        }
