"""The benchmark's one traffic generator.

A traffic mix is a JSON file of parameters beside this module
(``bench/traffic/<mix>.json``); a cell names the mix, its configuration
names the stream family. This module turns them into work:

* **The pool.** During set-up, ``P`` distinct synthetic DVS streams are
  drawn from the seed, stream ``i`` of class ``i mod n_classes``:
  class-conditioned analytic scenes with Poisson
  event counts on the fine sub-slot grid (a copy of the repository's
  synthetic generator, so the yardstick cannot move with the program),
  expanded into timestamped ``(t, x, y, p)`` events and cut into the
  engine's replay chunks. All streams are generated on the device in one
  jitted call and copied back once as ``uint8`` counts.
* **Replay.** :class:`ReplaySource` hands the pool to the served entry
  through the event-source contract (``iter_event_chunks``): the k-th
  stream opened replays pool entry ``k mod P``, with no generation on the
  serving path. It labels each stream with its pool index, so every
  served answer is matched to the reference of the events it was fed.
* **Arrivals.** :func:`plan` turns a mix into ``serve`` arguments: all
  streams offered up front (``upfront``), or an open loop of evenly
  spaced connections (``even``) at a rate fixed by the cell.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# ---------------------------------------------------------------------------
# scene model: a copy of the synthetic DVS generator (gesture / nmnist)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StreamSpec:
    """One stream family, as a configuration's ``stream`` block states it."""
    family: str              # "gesture" | "nmnist"
    height: int
    width: int
    n_classes: int
    duration_ms: float
    contrast_gain: float
    oversample: int
    blob_sigma: float
    seed_jitter: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "StreamSpec":
        s = cfg["stream"]
        return cls(family=s["family"], height=int(s["height"]),
                   width=int(s["width"]), n_classes=int(s["n_classes"]),
                   duration_ms=float(s["duration_ms"]),
                   contrast_gain=float(s["contrast_gain"]),
                   oversample=int(s["oversample"]),
                   blob_sigma=float(s["blob_sigma"]),
                   seed_jitter=bool(s["seed_jitter"]))


def _grid(spec: StreamSpec):
    ys = jnp.linspace(-1.0, 1.0, spec.height)
    xs = jnp.linspace(-1.0, 1.0, spec.width)
    return jnp.meshgrid(ys, xs, indexing="ij")


def _gesture_centers(t, label, phase):
    c = label.astype(jnp.float32)
    rot = (jnp.mod(c, 3.0) - 1.0)
    axis = 2.0 * math.pi * jnp.floor(c / 3.0) / 4.0
    speed = 1.0 + 0.5 * jnp.mod(jnp.floor(c / 3.0), 2.0)
    ang = 2.0 * math.pi * speed * t + phase
    r = 0.55
    osc = r * jnp.sin(ang)
    px = jnp.where(rot == 0.0, osc * jnp.cos(axis),
                   r * jnp.cos(rot * ang + axis))
    py = jnp.where(rot == 0.0, osc * jnp.sin(axis),
                   r * jnp.sin(rot * ang + axis))
    return px, py


def _nmnist_glyph(label):
    c = label.astype(jnp.float32)
    return math.pi * c / 10.0, math.pi * (0.5 + jnp.mod(c * 3.0, 10.0) / 10.0)


def _saccade(t):
    seg = jnp.clip(jnp.floor(t * 3.0), 0, 2)
    u = t * 3.0 - seg
    amp = 0.25
    vx = jnp.array([-amp, amp, 0.0, -amp])
    vy = jnp.array([-amp, -amp, amp, -amp])
    i = seg.astype(jnp.int32)
    return (vx[i] * (1 - u) + vx[i + 1] * u,
            vy[i] * (1 - u) + vy[i + 1] * u)


def _intensity(t, label, phase, spec: StreamSpec):
    yy, xx = _grid(spec)
    sig = spec.blob_sigma * 2.0
    if spec.family == "gesture":
        px, py = _gesture_centers(t, label, phase)
        return jnp.exp(-((xx - px) ** 2 + (yy - py) ** 2) / (2 * sig ** 2))
    if spec.family == "nmnist":
        a1, a2 = _nmnist_glyph(label)
        sx, sy = _saccade(t)
        out = jnp.zeros_like(xx)
        for a in (a1, a2):
            ux, uy = jnp.cos(a), jnp.sin(a)
            dx, dy = xx - sx, yy - sy
            along = dx * ux + dy * uy
            perp = -dx * uy + dy * ux
            out = out + jnp.exp(-(perp ** 2) / (2 * (sig * 0.4) ** 2)) * \
                jnp.exp(-(along ** 2) / (2 * 0.45 ** 2))
        return out
    raise ValueError(f"unknown stream family {spec.family!r}")


def _one_stream(key, label, spec: StreamSpec, n_total: int):
    """Counts ``[n_total, H, W, 2]`` of one stream of class ``label``
    from its key, drawn exactly as the repository's synthetic source
    draws a replayed sample of a given class (scene phase and Poisson
    counts from the second half of the key)."""
    _, ke = jax.random.split(key)
    kj, kp = jax.random.split(ke)
    phase = (jax.random.uniform(kj, (1,)) * 2 * math.pi
             if spec.seed_jitter else jnp.zeros((1,)))[0]
    m = spec.oversample
    dt = 1.0 / (n_total * m)

    def slot(pk, idx):
        pk, sk = jax.random.split(pk)
        ts = idx.astype(jnp.float32) / n_total + dt * jnp.arange(m + 1)
        frames = jax.vmap(lambda t: _intensity(t, label, phase, spec))(ts)
        d = jnp.diff(frames, axis=0)
        rates = jnp.stack([jnp.sum(jnp.maximum(d, 0.0), axis=0),
                           jnp.sum(jnp.maximum(-d, 0.0), axis=0)],
                          axis=-1)[None] * spec.contrast_gain
        counts = jax.random.poisson(sk, rates).astype(jnp.float32)
        return pk, counts[0]

    _, ev = lax.scan(slot, kp, jnp.arange(n_total))
    return ev


@partial(jax.jit, static_argnames=("spec", "n_total"))
def pool_counts(keys: jax.Array, labels: jax.Array, spec: StreamSpec,
                n_total: int):
    """Every pool stream in one device call: event counts ``[P, n_total,
    H, W, 2]`` as ``uint8`` (the largest count is returned too, so a
    count past 255 is refused, never wrapped)."""
    counts = jax.vmap(lambda k, c: _one_stream(k, c, spec, n_total))(
        keys, labels)
    return jnp.minimum(counts, 255).astype(jnp.uint8), counts.max()


# ---------------------------------------------------------------------------
# events: counts -> timestamped records -> replay chunks
# ---------------------------------------------------------------------------

class Events:
    """A bounded run of events in time order: ``t`` µs int64, ``x``/``y``
    int32 sensor coordinates, ``p`` int8 polarity (1 = ON). It has the
    fields and length of the program's event chunk."""
    __slots__ = ("t", "x", "y", "p")

    def __init__(self, t, x, y, p):
        self.t, self.x, self.y, self.p = t, x, y, p

    def __len__(self) -> int:
        return len(self.t)


def counts_to_events(counts: np.ndarray, slot_us: int) -> Events:
    """``[n_total, H, W, 2]`` counts (ON, OFF) → events. A cell holding
    ``c`` events spreads them evenly inside its slot, the k-th at
    ``k·slot_us // c``, so binning them back at ``slot_us`` recovers the
    counts exactly."""
    counts = np.asarray(counts)
    slot, y, x, pol = np.nonzero(counts)
    reps = counts[slot, y, x, pol].astype(np.int64)
    slot, y, x, pol = (np.repeat(a, reps) for a in (slot, y, x, pol))
    n = len(slot)
    starts = np.repeat(np.cumsum(reps) - reps, reps)
    rank = np.arange(n) - starts
    cell_count = np.repeat(reps, reps)
    off = np.minimum(rank * slot_us // np.maximum(cell_count, 1),
                     slot_us - 1)
    t = slot * slot_us + off
    order = np.argsort(t, kind="stable")
    return Events(t=t[order].astype(np.int64), x=x[order].astype(np.int32),
                  y=y[order].astype(np.int32),
                  p=(1 - pol[order]).astype(np.int8))


def chunk_events(ev: Events, chunk_us: int, n_chunks: int) -> list[Events]:
    """Cut a time-ordered record into ``n_chunks`` chunks of ``chunk_us``
    (gaps give empty chunks; events past the end are dropped)."""
    bounds = np.searchsorted(ev.t, np.arange(n_chunks + 1, dtype=np.int64)
                             * chunk_us)
    return [Events(ev.t[lo:hi], ev.x[lo:hi], ev.y[lo:hi], ev.p[lo:hi])
            for lo, hi in zip(bounds[:-1].tolist(), bounds[1:].tolist())]


@dataclass
class Pool:
    """P streams, made once per run in set-up."""
    spec: StreamSpec
    slot_us: int
    chunk_us: int
    labels: np.ndarray           # [P] class of each stream's scene
    counts: np.ndarray           # [P, n_total, H, W, 2] uint8
    chunks: list[list[Events]]   # [P][n_chunks]
    n_events: np.ndarray         # [P]

    @property
    def size(self) -> int:
        return len(self.chunks)


def stream_keys(key: jax.Array, n: int) -> jax.Array:
    """Key of pool stream ``i``: ``fold_in(key, i)``."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))


def make_pool(key: jax.Array, spec: StreamSpec, size: int, *,
              slot_us: int, chunk_us: int, log=None) -> Pool:
    n_total = int(round(spec.duration_ms * 1000)) // slot_us
    if n_total * slot_us != int(round(spec.duration_ms * 1000)):
        raise ValueError(f"slot of {slot_us} µs does not divide the "
                         f"{spec.duration_ms} ms stream")
    t0 = time.perf_counter()
    # every seed serves the same classes, so the same amount of work: the
    # seed moves the scenes' phases and the Poisson draws only
    labels = np.arange(size) % spec.n_classes
    counts, top = pool_counts(stream_keys(key, size), jnp.asarray(labels),
                              spec, n_total)
    if float(top) > 255:
        raise ValueError(f"a pool cell holds {float(top):.0f} events, "
                         f"more than uint8 counts keep")
    t1 = time.perf_counter()
    counts = np.asarray(counts)
    t2 = time.perf_counter()
    n_chunks = n_total * slot_us // chunk_us
    chunks, n_events = [], []
    for c in counts:
        ev = counts_to_events(c, slot_us)
        chunks.append(chunk_events(ev, chunk_us, n_chunks))
        n_events.append(len(ev))
    if log is not None:
        log(f"pool: device {t1 - t0:.3f} s, copy {t2 - t1:.3f} s, events "
            f"{time.perf_counter() - t2:.3f} s")
    return Pool(spec=spec, slot_us=slot_us, chunk_us=chunk_us,
                labels=labels, counts=counts, chunks=chunks,
                n_events=np.asarray(n_events, np.int64))


class ReplaySource:
    """The event-source contract over a pool: the k-th stream opened
    replays pool entry ``k mod P`` and is labelled ``k mod P``.
    ``duration_ms`` cuts every stream to its first milliseconds (a short
    warm-up cohort); by default streams run their whole duration."""

    def __init__(self, pool: Pool, *, duration_ms: float | None = None):
        spec = pool.spec
        self.pool = pool
        self.name = f"replay-{spec.family}"
        self.height, self.width = spec.height, spec.width
        self.sensor_hw = (spec.height, spec.width)
        self.n_classes = spec.n_classes
        self.duration_ms = (spec.duration_ms if duration_ms is None
                            else float(duration_ms))
        self._n_chunks = int(round(self.duration_ms * 1000)) // pool.chunk_us
        self.opened = 0

    def n_slots(self, t_intg_ms: float) -> int:
        n = self.duration_ms / t_intg_ms
        if abs(n - round(n)) > 1e-6:
            raise ValueError(f"T_INTG {t_intg_ms} ms does not divide the "
                             f"stream duration {self.duration_ms} ms")
        return int(round(n))

    def iter_event_chunks(self, key, *, chunk_us: int, slot_us=None):
        del key, slot_us
        if chunk_us != self.pool.chunk_us:
            raise ValueError(f"the pool is cut in {self.pool.chunk_us} µs "
                             f"chunks; the engine asks for {chunk_us}")
        i = self.opened % self.pool.size
        self.opened += 1
        return i, iter(self.pool.chunks[i][:self._n_chunks])


# ---------------------------------------------------------------------------
# arrivals
# ---------------------------------------------------------------------------

def plan(mix: dict, *, capacity: int, duration_s: float, seconds: float,
         cohort_s: float) -> dict:
    """``serve`` arguments for ``seconds`` of a mix.

    ``upfront``: every stream offered at once, so every lane is always
    full; whole cohorts of ``capacity`` streams, as many as fill about
    ``seconds`` at the measured ``cohort_s`` (at least one).
    ``even``: an open loop, one connection every ``duration_s /
    (load · capacity)`` seconds, so ``load · capacity`` sensors are
    connected on average; as many as connect in ``seconds -
    duration_s``, and ``max_pending_per_lane · capacity`` streams may
    wait for a lane."""
    arrivals = mix["arrivals"]
    if arrivals == "upfront":
        cohorts = max(1, round(seconds / max(cohort_s, 1e-9)))
        return {"n_streams": cohorts * capacity, "paced": bool(mix["paced"]),
                "offered_rate": None, "max_pending": None}
    if arrivals == "even":
        rate = mix["load"] * capacity / duration_s
        n = max(1, int(math.floor(rate * max(seconds - duration_s, 0.0))) + 1)
        return {"n_streams": n, "paced": bool(mix["paced"]),
                "offered_rate": rate,
                "max_pending": int(round(mix["max_pending_per_lane"]
                                         * capacity))}
    raise ValueError(f"unknown arrivals {arrivals!r}")
