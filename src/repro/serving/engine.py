"""Multi-stream streaming detection engine (the paper's deployment scenario).

The headline SHIELD8-UAV use case is *continuous* acoustic monitoring: raw
microphone audio arrives as an unbounded stream, is cut into 0.8 s windows,
each window is scored by the 1D-F-CNN on the W8A8 datapath, and the temporal
tracker turns the per-window probabilities into stable detection events.
This module scales that loop to N concurrent streams:

* **per-stream ring buffers** (:class:`StreamRing`) absorb raw audio pushed
  in arbitrary chunk sizes and emit hop-aligned 0.8 s windows;
* **continuous micro-batching** packs each round's ready windows into slot
  blocks of one jitted :func:`~repro.serving.accelerator.accelerator_forward`
  program (the detector's, or the HuBERT verifier's for a second stage) via
  the shared :class:`~repro.serving.batching.DispatchCore` (the same core
  ``launch/serve.py``'s ``BatchedServer`` runs on): fixed
  ``batch_slots`` blocks with silence-padded dead slots by default, or —
  with ``adaptive_slots=True`` — blocks grown/shrunk over a small
  pre-jittable ladder to fit the backlog, so one live stream dispatches a
  1-slot block instead of padding 7/8;
* **admission control** (:class:`~repro.serving.batching.AdmissionPolicy`)
  for fleet scale: cap the distinct streams admitted, cap windows drained
  per stream per round with a depth-fair round budget, and evict streams
  that persistently overflow their rings;
* a **vectorised tracker** (:class:`~repro.serving.tracker.VectorTemporalTracker`)
  advances all N streams' EMA/hysteresis/min-duration state in one numpy
  pass per round.

Because the accelerator path quantises activations with *per-sample* scales,
a window's probability is bitwise independent of whatever other streams it
was co-batched with — streaming one window at a time, 64 streams packed 8 to
a batch, or a batch split over a device mesh, produces the identical numbers
(the streaming-parity and sharded-conformance tests pin this).  ``shards=k``
routes every fixed-slot block through the ``shard_map``-based
:func:`~repro.serving.accelerator.accelerator_forward_sharded` (weights
replicated, activation rows split over a 1-D "streams" mesh), and every
block of a round is submitted before the first is harvested, so the device
works through the round's blocks while the host packs and launches the
next.  ``python -m repro.launch.monitor`` is the demo entry point and
``benchmarks/bench_serving.py`` the throughput harness on top of this class.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import features
from repro.distributed.sharding import stream_mesh
from repro.kernels.backend import resolve_interpret
from repro.models import hubert
from repro.models.cnn1d import CNNConfig
from repro.models.hubert import HubertConfig, HubertParams
from repro.serving.accelerator import (
    accelerator_forward,
    accelerator_forward_sharded,
    forward_scopes,
    precompile_slot_shapes,
)
from repro.serving.batching import (
    AdmissionPolicy,
    BlockPool,
    DispatchCore,
    SlotPolicy,
    fair_allocation,
)
from repro.serving.quantized_params import (
    QuantizedParams,
    quantize_params,
    replicate_params,
)
from repro.serving.telemetry import Telemetry
from repro.serving.tracker import TrackEvent, VectorTemporalTracker


class StreamRing:
    """Fixed-capacity ring buffer over one stream's raw samples.

    ``push`` accepts arbitrary chunk sizes; ``pop_window`` emits the next
    hop-aligned window of ``window`` samples and advances the read head by
    ``hop`` (overlapping windows when ``hop < window``).  On overflow the
    oldest *whole hops* are dropped (keeping the stream hop-aligned) and
    counted in ``dropped`` — an always-on monitor degrades, it never blocks.
    """

    def __init__(self, window: int, hop: int, capacity_windows: int = 8):
        # Real exceptions, not asserts: ingest validation must survive
        # ``python -O`` — an always-on monitor is exactly the deployment
        # where optimised bytecode would silently skip the checks.
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        if hop <= 0:
            raise ValueError(f"hop must be positive, got {hop}")
        if capacity_windows < 1:
            raise ValueError(
                f"capacity_windows must be >= 1, got {capacity_windows}"
            )
        self.window = window
        self.hop = hop
        self.capacity = window + (capacity_windows - 1) * hop
        self._buf = np.zeros(self.capacity, np.float32)
        self._w = 0  # absolute count of samples written
        self._r = 0  # absolute index of the next window's first sample
        self.dropped = 0  # samples lost to overflow

    @property
    def buffered(self) -> int:
        """Samples currently held between the read and write heads."""
        return self._w - self._r

    @property
    def ready(self) -> int:
        """Number of complete windows currently extractable."""
        avail = self._w - self._r
        return 0 if avail < self.window else 1 + (avail - self.window) // self.hop

    def push(self, samples: np.ndarray) -> int:
        """Append raw audio; returns the number of samples dropped (0 unless
        the buffer overflowed)."""
        x = np.asarray(samples, np.float32).reshape(-1)
        avail = self._w - self._r
        total = avail + len(x)
        dropped = 0
        if total > self.capacity:
            need = total - self.capacity
            dropped = min(((need + self.hop - 1) // self.hop) * self.hop, total)
            # Oldest first: consume buffered backlog, then (for a chunk
            # bigger than the whole buffer) the incoming head passes through
            # unrecorded — both read and write heads advance over it so the
            # stream stays hop-aligned end to end.
            drop_buffered = min(dropped, avail)
            self._r += drop_buffered
            skip = dropped - drop_buffered
            self._w += skip
            self._r += skip
            x = x[skip:]
            self.dropped += dropped
        pos = self._w % self.capacity
        first = min(len(x), self.capacity - pos)
        self._buf[pos : pos + first] = x[:first]
        self._buf[: len(x) - first] = x[first:]
        self._w += len(x)
        return dropped

    def peek_window(self) -> np.ndarray | None:
        """Next hop-aligned window *without* consuming it, or None if fewer
        than ``window`` samples are buffered.  Pair with :meth:`advance` once
        the window has actually been scored — the transactional round
        protocol the monitor engine uses so a failed forward never loses a
        window."""
        if self._w - self._r < self.window:
            return None
        return self.peek_windows(1)[0]

    def peek_windows(self, k: int) -> np.ndarray:
        """The next ``k`` hop-aligned windows *without* consuming them, as a
        ``(k, window)`` array — the multi-window generalisation of
        :meth:`peek_window` for a round that drains a backlog.  Raises if
        fewer than ``k`` complete windows are buffered."""
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        return self.copy_windows(np.empty((k, self.window), np.float32))

    def copy_windows(self, out: np.ndarray, first: int = 0) -> np.ndarray:
        """Copy the ``len(out)`` hop-aligned windows that start ``first``
        hops past the read head into the rows of ``out`` (a caller-given
        ``(k, window)`` array, e.g. rows of a dispatch block), *without*
        consuming them; returns ``out``.  Each window is one contiguous
        slice of the buffer, two when it wraps the buffer's end — no index
        array.  Raises if those windows are not all buffered."""
        k = len(out)
        if first < 0 or self.ready < first + k:
            raise ValueError(
                f"windows {first}..{first + k - 1} requested, only "
                f"{self.ready} ready"
            )
        buf, cap, win, hop = self._buf, self.capacity, self.window, self.hop
        pos = (self._r + first * hop) % cap
        for j in range(k):
            end = pos + win
            if end <= cap:
                out[j] = buf[pos:end]
            else:  # wraps the buffer's end
                out[j, : cap - pos] = buf[pos:]
                out[j, cap - pos :] = buf[: end - cap]
            pos = (pos + hop) % cap
        return out

    def advance(self):
        """Consume one hop off the front (commit the last peeked window)."""
        if self._w - self._r < self.window:
            raise ValueError("advance() without a complete window buffered")
        self._r += self.hop

    def pop_window(self) -> np.ndarray | None:
        """Next hop-aligned window, or None if fewer than ``window`` samples
        are buffered."""
        out = self.peek_window()
        if out is not None:
            self._r += self.hop
        return out

    # -- crash-recoverable state ---------------------------------------------

    def state_dict(self) -> dict:
        """Deep-copied snapshot: buffer contents plus the absolute read/write
        heads and the drop counter.  Restoring it reproduces the ring
        bitwise — every window popped after a restore is identical to the
        windows an uninterrupted ring would have popped."""
        return {
            "window": self.window,
            "hop": self.hop,
            "capacity": self.capacity,
            "buf": self._buf.copy(),
            "w": self._w,
            "r": self._r,
            "dropped": self.dropped,
        }

    def load_state_dict(self, sd: dict):
        for field in ("window", "hop", "capacity"):
            if sd[field] != getattr(self, field):
                raise ValueError(
                    f"state_dict {field}={sd[field]} does not match this "
                    f"ring's {field}={getattr(self, field)}"
                )
        self._buf = np.asarray(sd["buf"], np.float32).copy()
        self._w = int(sd["w"])
        self._r = int(sd["r"])
        self.dropped = int(sd["dropped"])


@dataclasses.dataclass(frozen=True)
class SanitizeReport:
    """What :meth:`SanitizePolicy.apply` did to one chunk."""

    rejected: bool = False  # chunk refused outright (reason below)
    reason: str | None = None  # "nonfinite" | "clipped" when rejected
    zeroed: int = 0  # non-finite samples replaced with 0.0
    clipped: bool = False  # chunk exceeded the clip-fraction threshold


@dataclasses.dataclass(frozen=True)
class SanitizePolicy:
    """Ingest hardening for one microphone chunk (the engine's ``push``).

    A field microphone that starts emitting NaN/Inf (broken ADC, saturated
    preamp, truncated UDP payload decoded as garbage) must degrade *its own*
    stream, never poison the fleet: a single NaN entering the ring would
    propagate through the forward into the tracker EMA, which never recovers
    (``0.4 * nan + 0.6 * ema`` is NaN forever).  The policy runs before any
    sample reaches the ring:

    * ``nonfinite="reject"`` drops a chunk containing any NaN/Inf sample;
      ``"zero"`` replaces just the poisoned samples with 0.0 and keeps the
      chunk (preserves window alignment at the cost of a dirty window).
    * ``clip_level``/``max_clip_fraction`` flag *clipped* chunks — more than
      ``max_clip_fraction`` of samples at or beyond ``clip_level`` full
      scale.  ``clipped_action="count"`` only counts them (clipping degrades
      features but is finite); ``"reject"`` drops the chunk.

    Per-stream reject/zero/clip counters live on the engine
    (``rejected_chunks``/``zeroed_samples``/``clipped_chunks``) so an
    operator can tell *which* microphone went bad and when.
    """

    nonfinite: str = "reject"  # "reject" | "zero"
    clip_level: float | None = None  # None disables clip detection
    max_clip_fraction: float = 0.05
    clipped_action: str = "count"  # "count" | "reject"

    def __post_init__(self):
        if self.nonfinite not in ("reject", "zero"):
            raise ValueError(
                f"nonfinite must be 'reject' or 'zero', got {self.nonfinite!r}"
            )
        if self.clipped_action not in ("count", "reject"):
            raise ValueError(
                f"clipped_action must be 'count' or 'reject', got "
                f"{self.clipped_action!r}"
            )
        if self.clip_level is not None and self.clip_level <= 0:
            raise ValueError(f"clip_level must be positive, got {self.clip_level}")
        if not 0.0 <= self.max_clip_fraction <= 1.0:
            raise ValueError(
                f"max_clip_fraction must be in [0, 1], got "
                f"{self.max_clip_fraction}"
            )

    def apply(self, x: np.ndarray) -> tuple[np.ndarray | None, SanitizeReport]:
        """Sanitize one chunk; returns ``(clean_chunk_or_None, report)``.
        The chunk is ``None`` exactly when the report says ``rejected``."""
        bad = ~np.isfinite(x)
        n_bad = int(bad.sum())
        if n_bad and self.nonfinite == "reject":
            return None, SanitizeReport(rejected=True, reason="nonfinite")
        clipped = False
        if self.clip_level is not None and len(x):
            finite_frac = float(
                np.mean(np.abs(np.where(bad, 0.0, x)) >= self.clip_level)
            )
            clipped = finite_frac > self.max_clip_fraction
            if clipped and self.clipped_action == "reject":
                return None, SanitizeReport(
                    rejected=True, reason="clipped", clipped=True
                )
        if n_bad:
            x = np.where(bad, np.float32(0.0), x)
        return x, SanitizeReport(zeroed=n_bad, clipped=clipped)


@dataclasses.dataclass
class WindowScore:
    """One scored window: raw probability plus the tracker's view of it."""

    stream: int
    window_idx: int  # per-stream window index (tracker idx)
    p_uav: float
    smoothed: float
    active: bool


class MonitorEngine:
    """N-stream continuous monitor over the served accelerator datapath.

    It serves either model kind of :mod:`repro.serving.accelerator`: the
    1D-F-CNN detector (a :class:`~repro.models.cnn1d.CNNConfig`, baked to a
    quantised artifact) or the HuBERT X-Large verifier (a
    :class:`~repro.models.hubert.HubertConfig` with ``feature_kind=
    "waveform"`` and ``precision="bf16"``, baked to a ``HubertParams``
    artifact).  Rings, blocks, dispatch, tracker and telemetry are the same
    for both; the verifier takes no ``prune``, ``policy``, ``shards`` or
    ``mesh``.

    ``push`` raw audio per stream in any chunking; each ``step`` scores at
    most one ready window per stream (one *round*), micro-batched through
    the jitted forward in fixed ``batch_slots`` chunks.  ``drain`` loops
    until no stream has a complete window left; ``finalize`` flushes the
    trackers and returns per-stream event lists.

    ``shards``/``mesh`` select sharded-batch dispatch (each block split over
    the mesh's "streams" axis, bitwise identical results).  Every block of a
    round is in flight before the first is harvested (``depth_histogram``).

    ``prune``/``policy`` bake a structured channel prune and a per-layer
    precision policy into the served artifact at construction time — the
    engine then serves the paper's deployed configuration (pruned flatten,
    mixed per-layer modes) with every parity guarantee intact.

    ``on_device_features=True`` fuses the DSP front-end into the jitted
    program: the engine submits raw ``(slots, 12800)`` window blocks and the
    artifact's baked ``feature_kind`` front-end runs in-graph, so host
    feature extraction no longer serializes with the queued device
    dispatch.  The numpy front-end stays the oracle: its float64 features
    differ from the in-graph float32 ones within a per-kind tolerance
    (``features_jax.PARITY_ATOL``), while all *within-JAX* parity guarantees
    (streaming == batched == sharded) remain bitwise.
    """

    def __init__(
        self,
        params: dict | QuantizedParams | HubertParams,
        cfg: CNNConfig | HubertConfig,
        *,
        n_streams: int,
        feature_kind: str = "mfcc20",
        on_device_features: bool = False,
        hop_samples: int | None = None,
        batch_slots: int = 8,
        precision: str = "int8",
        prune=None,  # PruneSpec baked into the served artifact
        policy=None,  # PrecisionPolicy resolving per-layer modes
        sanitize: SanitizePolicy | None = None,
        capacity_windows: int = 8,
        interpret: bool | None = None,
        shards: int | None = None,
        mesh: jax.sharding.Mesh | None = None,
        adaptive_slots: bool = False,
        min_slots: int = 1,
        admission: AdmissionPolicy | None = None,
        ema_alpha: float = 0.4,
        enter_threshold: float = 0.65,
        exit_threshold: float = 0.35,
        min_duration: int = 2,
    ):
        if cfg.input_len != features.FEATURE_DIMS[feature_kind]:
            raise ValueError(
                f"model input_len {cfg.input_len} != {feature_kind} feature "
                f"dim {features.FEATURE_DIMS[feature_kind]}"
            )
        if n_streams < 1:
            raise ValueError(f"n_streams must be >= 1, got {n_streams}")
        if batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {batch_slots}")
        self.cfg = cfg
        self.n_streams = n_streams
        self.feature_kind = feature_kind
        self.on_device_features = on_device_features
        self.batch_slots = batch_slots
        self.window = features.N_SAMPLES
        self.hop = hop_samples if hop_samples is not None else features.N_SAMPLES
        # Width of one micro-batch row: raw samples when the front-end is
        # fused into the device program, extracted features otherwise.
        self._in_width = features.N_SAMPLES if on_device_features else cfg.input_len
        self._interpret = resolve_interpret(interpret)
        # The served artifact: either pre-baked, or baked here from the fp32
        # checkpoint with the deployment decisions (default precision, prune
        # spec, per-layer policy, fused front-end) applied at quantise-once
        # time.
        if isinstance(cfg, HubertConfig):
            if precision != "bf16" or prune is not None or policy is not None:
                raise ValueError(
                    "the HuBERT verifier is served as one bf16 bake: pass "
                    "precision='bf16' and no prune or policy"
                )
            if shards is not None or mesh is not None:
                raise ValueError(
                    "the HuBERT verifier is served on one device: pass no "
                    "shards or mesh"
                )
            self._qp = params if isinstance(params, HubertParams) else hubert.bake(params, cfg)
        elif isinstance(params, QuantizedParams):
            if prune is not None or policy is not None:
                raise ValueError(
                    "prune/policy are quantise-once decisions and cannot be "
                    "applied to an already-baked QuantizedParams artifact; "
                    "pass the fp32 checkpoint instead"
                )
            if on_device_features and params.feature_kind != feature_kind:
                raise ValueError(
                    f"on_device_features=True needs an artifact baked for "
                    f"feature kind {feature_kind!r}, got "
                    f"{params.feature_kind!r}; re-bake with "
                    f"quantize_params(..., feature_kind={feature_kind!r})"
                )
            self._qp = params
        else:
            self._qp = quantize_params(
                params, cfg, mode=precision, prune=prune, policy=policy,
                feature_kind=feature_kind if on_device_features else None,
            )
        # Sharded-batch dispatch: split each fixed-slot block along a 1-D
        # device mesh ("streams" axis), weights replicated.  `shards=None`
        # keeps the single-device path; `shards=k` (including k=1, useful to
        # measure shard_map overhead) routes every forward through
        # accelerator_forward_sharded.
        if mesh is None and shards is not None:
            mesh = stream_mesh(shards)
        self._mesh = mesh
        self._mesh_axis = None
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    f"MonitorEngine needs a 1-D mesh (one batch-sharding "
                    f"axis), got axes {mesh.axis_names}"
                )
            if shards is not None and mesh.devices.size != shards:
                raise ValueError(
                    f"mesh has {mesh.devices.size} device(s) but shards="
                    f"{shards}; pass one or make them agree"
                )
            self._mesh_axis = mesh.axis_names[0]
            n_shards = mesh.shape[self._mesh_axis]
            if batch_slots % n_shards != 0:
                raise ValueError(
                    f"batch_slots {batch_slots} must divide evenly over "
                    f"{n_shards} shards"
                )
            self._qp = replicate_params(self._qp, mesh)
        self.shards = 1 if mesh is None else mesh.shape[self._mesh_axis]
        self._rings = [
            StreamRing(self.window, self.hop, capacity_windows)
            for _ in range(n_streams)
        ]
        self.tracker = VectorTemporalTracker(
            n_streams,
            ema_alpha=ema_alpha,
            enter_threshold=enter_threshold,
            exit_threshold=exit_threshold,
            min_duration=min_duration,
        )
        # The shared continuous-batching core (serving/batching.py): ladder
        # of dispatchable slot shapes (fixed = always batch_slots, adaptive
        # = power-of-two multiples of the shard count), the preallocated
        # block buffers (one per block of a round), and the slot-chunked
        # dispatch loop with the fault seam — the machinery launch/serve.py's
        # BatchedServer runs on too.  Dispatch is as deep as the round
        # (see ``_forward``).
        self.slot_policy = SlotPolicy(
            batch_slots,
            adaptive=adaptive_slots,
            min_slots=min_slots,
            multiple=self.shards,
        )
        self.adaptive_slots = self.slot_policy.adaptive
        self._pool = BlockPool(self._in_width)
        self._core = DispatchCore(
            submit=self._submit_rows,
            harvest=self._harvest,
            slot_policy=self.slot_policy,
        )
        # Stream admission / per-tenant fairness: the defaults reproduce the
        # classic behaviour (every stream admitted, one window per stream
        # per round, no budget, no eviction) exactly.
        self.admission = admission if admission is not None else AdmissionPolicy()
        self._admitted = np.ones(n_streams, bool)
        self._seen = np.zeros(n_streams, bool)
        self._n_seen = 0
        self._overflow_rounds = np.zeros(n_streams, np.int64)
        self._dropped_since_round = np.zeros(n_streams, np.int64)
        self._pending_evictions: list[int] = []
        # Incremental ready-window counts, updated O(1) on push/commit so a
        # 1,024-stream step() selects candidates with one vectorised compare
        # instead of rescanning every ring every round.
        self._ready_counts = np.zeros(n_streams, np.int64)
        # Ingest hardening: the sanitize policy runs on every push, per-
        # stream counters record what it did (None = trust the transport).
        self.sanitize = sanitize
        self.rejected_chunks = np.zeros(n_streams, np.int64)
        self.zeroed_samples = np.zeros(n_streams, np.int64)
        self.clipped_chunks = np.zeros(n_streams, np.int64)
        # observability counters for the bench / driver (forward_calls,
        # padded_slots and slot_histogram live on the core, exposed below)
        self.windows_scored = 0
        self.rounds = 0  # successfully committed scoring rounds
        self._dropped_samples = 0  # maintained incrementally by push()
        self.served_windows = np.zeros(n_streams, np.int64)
        self.deferred_windows = np.zeros(n_streams, np.int64)
        self.refused_chunks = np.zeros(n_streams, np.int64)
        # Host spans at every boundary of push/step (off by default; not
        # part of snapshot()): engine.push counts samples, engine.step the
        # windows scored, engine.gather (the round's window plan, or the
        # stacked windows and host features when off-device) the bytes the
        # round reads, engine.pack (filling the blocks) the live rows,
        # engine.put the bytes handed to the device,
        # engine.launch and engine.wait the slots, engine.tracker and
        # engine.commit the windows.
        self.telemetry = Telemetry()

    # -- ingest --------------------------------------------------------------

    def push(self, stream: int, samples: np.ndarray) -> int:
        """Append raw audio to one stream; returns samples dropped (overflow).

        Admission gate: the first ``admission.max_streams`` *distinct*
        streams ever pushed are admitted; chunks for later streams — and for
        streams the engine has evicted — are refused (counted in
        ``refused_chunks``, returns 0) without touching any ring."""
        tel = self.telemetry
        if tel.on:
            i = tel.open("engine.push", self.rounds)
            dropped = self._push(stream, samples)
            tel.close(i, np.asarray(samples).size)
            return dropped
        return self._push(stream, samples)

    def _push(self, stream: int, samples: np.ndarray) -> int:
        if not 0 <= stream < self.n_streams:
            raise ValueError(
                f"stream index {stream} out of range for an engine with "
                f"{self.n_streams} stream(s) (valid: 0..{self.n_streams - 1})"
            )
        if not self._seen[stream]:
            self._seen[stream] = True
            self._n_seen += 1
            max_streams = self.admission.max_streams
            if max_streams is not None and self._n_seen > max_streams:
                self._admitted[stream] = False
        if not self._admitted[stream]:
            self.refused_chunks[stream] += 1
            return 0  # refused at admission: nothing reached the ring
        x = np.asarray(samples, np.float32).reshape(-1)
        if self.sanitize is not None:
            x, rep = self.sanitize.apply(x)
            self.zeroed_samples[stream] += rep.zeroed
            if rep.clipped:
                self.clipped_chunks[stream] += 1
            if rep.rejected:
                self.rejected_chunks[stream] += 1
                return 0  # nothing reached the ring, nothing overflowed
        ring = self._rings[stream]
        dropped = ring.push(x)
        self._dropped_samples += dropped
        if dropped:
            self._dropped_since_round[stream] += dropped
        self._ready_counts[stream] = ring.ready
        return dropped

    def ready_windows(self) -> np.ndarray:
        """Per-stream count of complete, unscored windows (maintained
        incrementally on push/commit — no ring scan)."""
        return self._ready_counts.copy()

    @property
    def dropped_samples(self) -> int:
        return self._dropped_samples

    @property
    def admitted(self) -> np.ndarray:
        """Per-stream admission mask (False = refused at cap or evicted)."""
        return self._admitted.copy()

    def take_evictions(self) -> list[int]:
        """Stream ids evicted since the last call (overflow eviction); the
        fleet supervisor consumes these to rebuild the worker without the
        abusive streams via its reassignment machinery."""
        out, self._pending_evictions = self._pending_evictions, []
        return out

    # -- core counter shims (the dispatch loop lives in serving/batching) ----

    @property
    def fault_hook(self):
        """Fault-injection seam: when set, called with the round's items at
        the top of each dispatch, before anything is submitted — it may
        raise (simulated crash) or advance a fake clock (simulated stall).
        The transactional step() guarantees a raising hook leaves rings and
        tracker untouched.  Delegates to the shared core's ``pre_dispatch``;
        installed by the fleet supervisor's fault harness, never set in
        production serving."""
        return self._core.pre_dispatch

    @fault_hook.setter
    def fault_hook(self, hook):
        self._core.pre_dispatch = hook

    @property
    def forward_calls(self) -> int:
        return self._core.blocks_dispatched

    @forward_calls.setter
    def forward_calls(self, v: int):
        self._core.blocks_dispatched = int(v)

    @property
    def padded_slots(self) -> int:
        return self._core.padded_slots

    @padded_slots.setter
    def padded_slots(self, v: int):
        self._core.padded_slots = int(v)

    @property
    def slot_histogram(self) -> dict[int, int]:
        """Blocks dispatched per slot shape (adaptive sizing observability)."""
        return dict(self._core.slot_histogram)

    @property
    def depth_histogram(self) -> dict[int, int]:
        """``{blocks in flight at the round's first harvest: rounds}``: the
        dispatch depth, which is each round's block count."""
        return dict(self._core.depth_histogram)

    # -- scoring -------------------------------------------------------------

    def _submit(self, block: np.ndarray) -> jax.Array:
        """Dispatch one slot block; returns the in-flight device buffer
        (jax dispatch is async — this does not wait for the result)."""
        tel = self.telemetry
        if tel.on:
            i = tel.open("engine.put")
        x = jnp.asarray(block)
        if tel.on:
            tel.close(i, block.nbytes)
            i = tel.open("engine.launch")
        raw = self.on_device_features
        if self._mesh is not None:
            out = accelerator_forward_sharded(
                self._qp, x, self.cfg, mesh=self._mesh,
                axis_name=self._mesh_axis, interpret=self._interpret,
                raw_windows=raw,
            )
        else:
            out = accelerator_forward(
                self._qp, x, self.cfg, interpret=self._interpret, raw_windows=raw
            )
        # Queue the result's copy to the host behind the forward, so the
        # round's in-order harvest finds each block's probabilities there
        # instead of paying one device -> host round trip a block.
        out.copy_to_host_async()
        if tel.on:
            tel.close(i, block.shape[0])
        return out

    def _submit_rows(self, items, slots: int) -> jax.Array:
        """DispatchCore submit hook: fill a buffer of the chosen slot shape
        that no in-flight block owns with the live items and dispatch it.
        With the front-end on the device an item is a ``(ring, depth)`` pair
        of the round's window plan, and the ring copies that window straight
        into its row of the block; otherwise an item is a feature row."""
        tel = self.telemetry
        if tel.on:
            i = tel.open("engine.pack")
        if self.on_device_features:
            block = self._pool.buffer(slots, len(items))
            for j, (ring, depth) in enumerate(items):
                ring.copy_windows(block[j : j + 1], depth)
        else:
            block = self._pool.pack(items, slots)
        if tel.on:
            tel.close(i, len(items))
        return self._submit(block)

    def _harvest(self, buf: jax.Array) -> np.ndarray:
        """DispatchCore harvest hook: wait for one block, copy it to host."""
        tel = self.telemetry
        if tel.on:
            i = tel.open("engine.wait")
        out = np.asarray(buf.block_until_ready())
        if tel.on:
            tel.close(i, buf.shape[0])
        return out

    def _forward(self, items) -> np.ndarray:
        """Micro-batch the round's items — ``(n, row_width)`` feature rows,
        or the ``(ring, depth)`` window plan when the front-end is fused —
        through the shared dispatch core: the slot policy picks each block's
        shape (fixed ``batch_slots``, or the adaptive ladder), each block
        gets a preallocated buffer of its own, and every block of the round
        is submitted before the first harvest-time ``block_until_ready``.
        The core returns, or raises, only with no block in flight, so the
        round starts by making every buffer available again."""
        self._pool.rewind()
        return np.stack(self._core.dispatch(list(items)))

    def precompile(self) -> tuple[int, ...]:
        """Trace the jitted forward once per dispatchable slot shape (the
        policy's ladder) so adaptive serving never hits a compile stall
        mid-round; returns the ladder."""
        precompile_slot_shapes(
            self._qp,
            self.cfg,
            self.slot_policy.ladder,
            row_width=self._in_width,
            mesh=self._mesh,
            axis_name=self._mesh_axis,
            interpret=self._interpret,
            raw_windows=self.on_device_features,
        )
        return self.slot_policy.ladder

    def op_scopes(self) -> dict[str, str]:
        """``{HLO instruction name: layer scope}`` of the forward compiled at
        every dispatchable slot shape (the CNN's ``frontend``, ``conv<i>``,
        ``flatten``, ``dense<i>``, ``softmax``; the verifier's ``frontend``,
        ``waveform``, ``featproj``, ``posconv``, ``attn``, ``ffn``,
        ``head``): what maps the operations of a device trace to the layers
        of the model."""
        return forward_scopes(
            self._qp,
            self.cfg,
            self.slot_policy.ladder,
            row_width=self._in_width,
            mesh=self._mesh,
            axis_name=self._mesh_axis,
            interpret=self._interpret,
            raw_windows=self.on_device_features,
        )

    def step(self) -> list[WindowScore]:
        """Score one round over the admitted backlog.

        With the default :class:`~repro.serving.batching.AdmissionPolicy`
        this is the classic beat — at most one ready window per stream,
        every admitted stream served.  ``max_per_stream_per_round`` lets a
        backlogged stream drain several windows in one round;
        ``round_budget`` caps the round's total windows, allocated
        depth-fair (:func:`~repro.serving.batching.fair_allocation`) so a
        firehose stream can never displace another stream's first window.
        Windows beyond a stream's allocation stay buffered and are counted
        in ``deferred_windows``.

        Transactional: the round either completes — windows scored, rings
        advanced, tracker updated — or, if the forward raises, leaves every
        ring and the tracker exactly as they were (windows are *peeked* and
        only committed after scoring).  A supervisor that catches the raise
        can simply call ``step()`` again: the same windows are re-scored and
        the per-stream window indices never desync.

        Returns the per-window scores of this round (empty when no admitted
        stream had a complete window buffered).
        """
        tel = self.telemetry
        if tel.on:
            i = tel.open("engine.step", self.rounds, step=True)
            out = self._step(tel)
            tel.close(i, len(out))
            return out
        return self._step(tel)

    def _step(self, tel: Telemetry) -> list[WindowScore]:
        adm = self.admission
        cand = np.flatnonzero((self._ready_counts > 0) & self._admitted)
        if cand.size == 0:
            return []
        ready = self._ready_counts[cand]
        want = np.minimum(ready, adm.max_per_stream_per_round)
        alloc = fair_allocation(want, adm.round_budget)
        # Stream-major: stream cand[i] contributes alloc[i] consecutive
        # windows starting at offs[i].
        offs = np.zeros(cand.size, np.int64)
        np.cumsum(alloc[:-1], out=offs[1:])
        n_win = int(alloc.sum())
        if tel.on:
            i = tel.open("engine.gather")
        rings = self._rings
        if self.on_device_features:
            # The window plan: each block's rows are copied ring -> block
            # just before that block is submitted (``_submit_rows``).
            items = [
                (rings[s], d)
                for s, k in zip(cand.tolist(), alloc.tolist())
                for d in range(k)
            ]
        else:
            stacked = np.concatenate(
                [rings[s].peek_windows(int(k)) for s, k in zip(cand, alloc) if k]
            )
            items = features.batch_features(stacked, self.feature_kind)
        if tel.on:
            tel.close(i, n_win * self.window * 4)  # float32 bytes the round reads
        p_uav = self._forward(items)[:, 1]  # may raise: nothing committed yet
        if tel.on:
            i = tel.open("engine.tracker")
        # Tracker rounds go depth by depth — every served stream's d-th
        # window lands in one masked vector update — so each stream's
        # probability sequence reaches its EMA in exactly push order and the
        # numbers stay bitwise identical to scoring one window per round.
        out: list[WindowScore] = []
        for d in range(int(alloc.max())):
            m = alloc > d
            sel = cand[m]
            full = np.zeros(self.n_streams, np.float64)
            mask = np.zeros(self.n_streams, bool)
            full[sel] = p_uav[offs[m] + d]  # exact float32 -> float64 widening
            mask[sel] = True
            state = self.tracker.update(full, mask)
            out.extend(
                WindowScore(
                    stream=int(s),
                    window_idx=int(state["idx"][s]),
                    p_uav=float(full[s]),
                    smoothed=float(state["smoothed"][s]),
                    active=bool(state["active"][s]),
                )
                for s in sel
            )
        if tel.on:
            tel.close(i, n_win)
            i = tel.open("engine.commit")
        # Commit: consume the scored windows only now that the forward and
        # the tracker rounds all succeeded.
        for s, k in zip(cand, alloc):
            for _ in range(int(k)):
                self._rings[s].advance()
            self._ready_counts[s] = self._rings[s].ready
        self.windows_scored += n_win
        self.rounds += 1
        self.served_windows[cand] += alloc
        self.deferred_windows[cand] += ready - alloc
        # Overflow eviction: a stream whose ring dropped samples in
        # ``evict_overflow_rounds`` consecutive committed rounds is
        # de-admitted; the supervisor collects it via take_evictions().
        overflowed = self._dropped_since_round > 0
        self._overflow_rounds = np.where(overflowed, self._overflow_rounds + 1, 0)
        self._dropped_since_round[:] = 0
        if adm.evict_overflow_rounds is not None:
            evict = np.flatnonzero(
                self._admitted
                & (self._overflow_rounds >= adm.evict_overflow_rounds)
            )
            for s in evict:
                self._admitted[s] = False
                self._pending_evictions.append(int(s))
        if tel.on:
            tel.close(i, n_win)
        return out

    def drain(self) -> list[WindowScore]:
        """Run rounds until every buffered window has been scored."""
        out: list[WindowScore] = []
        while True:
            scored = self.step()
            if not scored:
                return out
            out.extend(scored)

    def finalize(self) -> list[list[TrackEvent]]:
        """Flush still-open tracks; returns per-stream event lists."""
        return self.tracker.finalize()

    # -- crash recovery ------------------------------------------------------

    def snapshot(self) -> dict:
        """Deep-copied snapshot of all serving state: every ring's buffer and
        read/write heads, the tracker's per-stream arrays and emitted events,
        and the observability counters.

        The contract (pinned by the fault-tolerance conformance tests): a
        fresh engine built from the *same baked artifact* that ``restore``s
        this snapshot and then receives the same pushes produces window
        scores and ``TrackEvent`` lists bitwise identical to the engine that
        never died.  Weights are deliberately NOT part of the snapshot — the
        artifact is immutable and shared, so a supervisor rebuilds workers
        from it and restores only the cheap mutable state.

        ``pending_evictions`` (streams de-admitted but not yet collected via
        :meth:`take_evictions`) is part of the snapshot: without it a revive
        from a snapshot taken between the de-admission and the collection
        would leave the stream de-admitted but never actually evicted — no
        event stash, a stale supervisor route, pushes journaled forever."""
        return {
            "rings": [r.state_dict() for r in self._rings],
            "pending_evictions": [int(s) for s in self._pending_evictions],
            "tracker": self.tracker.state_dict(),
            "counters": {
                "windows_scored": self.windows_scored,
                "forward_calls": self.forward_calls,
                "padded_slots": self.padded_slots,
                "rounds": self.rounds,
                "dropped_samples": self._dropped_samples,
                "rejected_chunks": self.rejected_chunks.copy(),
                "zeroed_samples": self.zeroed_samples.copy(),
                "clipped_chunks": self.clipped_chunks.copy(),
                "served_windows": self.served_windows.copy(),
                "deferred_windows": self.deferred_windows.copy(),
                "refused_chunks": self.refused_chunks.copy(),
                "overflow_rounds": self._overflow_rounds.copy(),
                "dropped_since_round": self._dropped_since_round.copy(),
                "admitted": self._admitted.copy(),
                "seen": self._seen.copy(),
            },
        }

    def restore(self, snap: dict):
        """Load a :meth:`snapshot` into this engine (same ``n_streams`` and
        window/hop geometry required)."""
        if len(snap["rings"]) != self.n_streams:
            raise ValueError(
                f"snapshot holds {len(snap['rings'])} stream(s) but this "
                f"engine was built for {self.n_streams}"
            )
        for ring, sd in zip(self._rings, snap["rings"]):
            ring.load_state_dict(sd)
        self.tracker.load_state_dict(snap["tracker"])
        c = snap["counters"]
        self.windows_scored = int(c["windows_scored"])
        self.forward_calls = int(c["forward_calls"])
        self.padded_slots = int(c["padded_slots"])
        self.rounds = int(c["rounds"])
        self._dropped_samples = int(c["dropped_samples"])
        self.rejected_chunks = np.asarray(c["rejected_chunks"], np.int64).copy()
        self.zeroed_samples = np.asarray(c["zeroed_samples"], np.int64).copy()
        self.clipped_chunks = np.asarray(c["clipped_chunks"], np.int64).copy()
        self.served_windows = np.asarray(c["served_windows"], np.int64).copy()
        self.deferred_windows = np.asarray(c["deferred_windows"], np.int64).copy()
        self.refused_chunks = np.asarray(c["refused_chunks"], np.int64).copy()
        self._overflow_rounds = np.asarray(c["overflow_rounds"], np.int64).copy()
        self._dropped_since_round = np.asarray(
            c["dropped_since_round"], np.int64
        ).copy()
        self._admitted = np.asarray(c["admitted"], bool).copy()
        self._seen = np.asarray(c["seen"], bool).copy()
        self._n_seen = int(self._seen.sum())
        # ``.get``: snapshots from before pending evictions were recorded
        # restore with none pending (their supervisors drained eagerly).
        self._pending_evictions = [
            int(s) for s in snap.get("pending_evictions", [])
        ]
        # ready counts are derived state: recompute from the restored rings
        self._ready_counts = np.array([r.ready for r in self._rings], np.int64)

    def snapshot_bytes(self) -> bytes:
        """:meth:`snapshot` serialised through the exact on-disk codec
        (:func:`repro.serving.durability.dumps_state`): dtypes, shapes and
        scalar counters survive the byte round-trip bit-for-bit."""
        from repro.serving.durability import dumps_state

        return dumps_state(self.snapshot())

    def restore_bytes(self, data: bytes) -> None:
        """Inverse of :meth:`snapshot_bytes`."""
        from repro.serving.durability import loads_state

        self.restore(loads_state(data))
