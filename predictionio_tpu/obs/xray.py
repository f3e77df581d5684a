"""``pio xray``: training made as observable as serving.

PRs 3 and 6 gave every *query* a phase waterfall, SLOs, and a perf gate;
training was still a black box — ``run_train``, the stream fold-in loop,
and the ``parallel/`` mesh path emitted no step timings, no memory
numbers, and no sharding evidence. ALX (PAPERS.md) ships pod-scale ALS by
reasoning explicitly about per-chip factor-table placement and step cost;
this module builds the same instruments for the framework:

- :class:`TrainProfile` — a **training step profiler**. Trainers run
  inside one recorder that captures a per-iteration timeline of phases
  (``host_etl`` / ``sweep`` / ``solve`` / ``eval``; open vocabulary with
  those four canonical) with monotonic wall time, device time (through
  :meth:`TrainProfile.device_barrier` / ``timed_block_until_ready``),
  rows/s throughput, and a per-iteration convergence metric. The phases
  **tile the measured train wall clock** (the contract tests assert the
  attributed sum lands within 10% — the same contract style as the PR-6
  serving waterfall), export as ``pio_train_*`` metrics + ``train.step``
  spans, and serialize as a compact JSON profile that every registry
  publish attaches to its :class:`~predictionio_tpu.registry.ModelManifest`.
- :func:`estimate_factors` — an **HBM capacity planner**: predicted
  per-device bytes for the factor tables and solver workspace of an ALS
  train over a mesh, cross-checked at runtime against
  ``jax.live_arrays()`` (:func:`live_array_bytes` /
  :func:`live_bytes_per_device`). Surfaced as ``pio doctor --capacity``
  so ROADMAP item 1's "10M+ users without exceeding per-device HBM"
  becomes a preflight answer instead of an OOM.
- a **sharding inspector** — given a pjit'd train step over a
  ``parallel/mesh.py`` mesh, report each array's axis→mesh placement
  (:func:`describe_shardings`), flag fully-replicated large arrays
  (:func:`find_replicated`), and count collectives in the compiled HLO
  (:func:`count_collectives`) so an unintended all-gather is a number in
  ``MULTICHIP_r*.json``, not a surprise on the pod.

Profiles flow through a contextvar (:func:`use_profile` /
:func:`current_profile`): trainer code calls the module-level
:func:`phase` / :func:`device_fetch` helpers, which no-op when nothing is
recording — the un-profiled path stays fully async.

jax is imported lazily; constructing a profile or running the capacity
planner costs nothing on processes that never touch a device
(``pio doctor --capacity`` is pure arithmetic).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import sys
import time
from typing import Any, Callable, Iterator

from predictionio_tpu.obs.jaxprof import install_jax_monitoring, monitoring_totals
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.tracing import Tracer, get_tracer

# canonical phase vocabulary (open: trainers may add more, these four are
# what the docs tables and `pio top` expect)
PHASE_HOST_ETL = "host_etl"  # event-store reads, packing, uploads, serialize
PHASE_SWEEP = "sweep"  # the alternating half-solves / fold-in absorbs
PHASE_SOLVE = "solve"  # whole-algorithm train when not iteration-split
PHASE_EVAL = "eval"  # convergence / drift evaluation

TRAIN_PHASES: tuple[str, ...] = (PHASE_HOST_ETL, PHASE_SWEEP, PHASE_SOLVE, PHASE_EVAL)

# per-step timeline entries kept in the serialized profile; aggregates are
# exact regardless (a 10k-iteration train must not ship a 10k-row JSON)
DEFAULT_TIMELINE_CAP = 256


@dataclasses.dataclass
class _PhaseAgg:
    wall_s: float = 0.0
    device_s: float = 0.0
    count: int = 0


def register_train_metrics(registry: MetricsRegistry) -> dict[str, Any]:
    """Get-or-create the ``pio_train_*`` metric family on a registry.
    Idempotent; shared by every :class:`TrainProfile` bound to the same
    registry, and called eagerly by surfaces that export the family
    (``StreamInstruments``) so the documented metrics exist — with zero
    series — before the first train step lands."""
    return {
        "steps": registry.counter(
            "pio_train_steps_total",
            "training iterations (batch sweeps / stream fold-in batches)",
            labelnames=("trainer",),
        ),
        "phase": registry.histogram(
            "pio_train_phase_seconds",
            "per-occurrence training phase wall time "
            "(host_etl|sweep|solve|eval; exclusive/self time)",
            labelnames=("trainer", "phase"),
        ),
        "device": registry.counter(
            "pio_train_device_seconds_total",
            "device time accounted inside training phases "
            "(barrier-confirmed fetches)",
            labelnames=("trainer", "phase"),
        ),
        "rows": registry.counter(
            "pio_train_rows_total",
            "training rows/examples processed",
            labelnames=("trainer",),
        ),
        "active": registry.gauge(
            "pio_train_active",
            "1 while this trainer's profile is measuring",
            labelnames=("trainer",),
        ),
        "phase_g": registry.gauge(
            "pio_train_phase",
            "1 for the phase this trainer is currently executing",
            labelnames=("trainer", "phase"),
        ),
        "peak": registry.gauge(
            "pio_train_peak_bytes_per_device",
            "peak live device bytes sampled during training (busiest device)",
            labelnames=("trainer",),
        ),
        "est": registry.gauge(
            "pio_train_est_bytes_per_device",
            "capacity-planner predicted per-device bytes "
            "(obs.xray.estimate_factors)",
            labelnames=("trainer",),
        ),
    }


class TrainProfile:
    """Per-train recorder: phases, steps, device time, memory, lineage.

    Wall clock accumulates only inside :meth:`measure` blocks, so a
    stream pipeline that folds a publish-span across many cycles (with
    sleeps in between) still satisfies the tiling contract. Phases nest
    with *exclusive* (self-time) semantics: a ``host_etl`` pack inside a
    ``solve`` block attributes to ``host_etl``, never double-counts.

    Not thread-safe by design — one profile records one trainer's loop
    (the contextvar keeps concurrent trains on separate profiles).
    """

    def __init__(
        self,
        trainer: str,
        registry: MetricsRegistry | None = None,
        tracer: Tracer | None = None,
        timeline_cap: int = DEFAULT_TIMELINE_CAP,
        clock: Callable[[], float] = time.perf_counter,
    ):
        self.trainer = trainer
        self.registry = registry
        self.tracer = tracer or get_tracer()
        self.timeline_cap = max(1, timeline_cap)
        self._clock = clock
        self.phases: dict[str, _PhaseAgg] = {}
        self.timeline: list[dict[str, Any]] = []
        self.timeline_truncated = False
        self.steps_total = 0
        self.rows_total = 0
        self.device_s = 0.0
        self.peak_live_bytes = 0
        self.peak_bytes_per_device = 0
        self.device_memory_stats: dict[str, Any] | None = None
        self.devices: dict[str, Any] | None = None
        self.estimate: CapacityEstimate | None = None
        self.finished = False
        self._wall_s = 0.0
        self._measure_t0: float | None = None
        self._phase_stack: list[list[Any]] = []  # [name, t0, child_elapsed]
        self._step_rec: dict[str, Any] | None = None
        if "jax" in sys.modules:
            # the compile totals below read zero unless the listeners are
            # registered; a profile never imports jax itself
            install_jax_monitoring()
        self._xla0 = monitoring_totals()
        self.xla_compiles = 0
        self.xla_compile_s = 0.0
        if registry is not None:
            m = register_train_metrics(registry)
            self._m_steps = m["steps"]
            self._m_phase = m["phase"]
            self._m_device = m["device"]
            self._m_rows = m["rows"]
            self._m_active = m["active"]
            self._m_phase_g = m["phase_g"]
            self._m_peak = m["peak"]
            self._m_est = m["est"]

    # ----------------------------------------------------------- measuring
    def resume(self) -> None:
        if self.finished or self._measure_t0 is not None:
            return
        self._measure_t0 = self._clock()
        if self.registry is not None:
            self._m_active.set(1.0, trainer=self.trainer)

    def pause(self) -> None:
        if self._measure_t0 is None:
            return
        self._wall_s += self._clock() - self._measure_t0
        self._measure_t0 = None
        if self.registry is not None:
            self._m_active.set(0.0, trainer=self.trainer)

    @contextlib.contextmanager
    def measure(self) -> Iterator["TrainProfile"]:
        """Accumulate wall clock for the duration of the block."""
        self.resume()
        try:
            yield self
        finally:
            self.pause()

    @property
    def wall_s(self) -> float:
        if self._measure_t0 is not None:
            return self._wall_s + (self._clock() - self._measure_t0)
        return self._wall_s

    @property
    def attributed_s(self) -> float:
        """Wall time covered by phases — the tiling-contract numerator."""
        return sum(p.wall_s for p in self.phases.values())

    # -------------------------------------------------------------- phases
    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Record a phase with exclusive-time nesting semantics."""
        frame: list[Any] = [name, self._clock(), 0.0]
        self._phase_stack.append(frame)
        if self.registry is not None:
            self._m_phase_g.set(1.0, trainer=self.trainer, phase=name)
        try:
            yield
        finally:
            self._phase_stack.pop()
            elapsed = self._clock() - frame[1]
            self_s = max(0.0, elapsed - frame[2])
            if self._phase_stack:
                # parent sees the whole nested interval as child time
                self._phase_stack[-1][2] += elapsed
            agg = self.phases.setdefault(name, _PhaseAgg())
            agg.wall_s += self_s
            agg.count += 1
            if self._step_rec is not None:
                ph = self._step_rec["phases"]
                ph[name] = ph.get(name, 0.0) + self_s
            if self.registry is not None:
                self._m_phase.observe(self_s, trainer=self.trainer, phase=name)
                self._m_phase_g.set(0.0, trainer=self.trainer, phase=name)
                if self._phase_stack:
                    self._m_phase_g.set(
                        1.0, trainer=self.trainer, phase=self._phase_stack[-1][0]
                    )

    # --------------------------------------------------------------- steps
    @contextlib.contextmanager
    def step(self, **tags: Any) -> Iterator[dict[str, Any]]:
        """One training iteration: a ``train.step`` span plus a timeline
        record. The yielded dict is the record — set ``metric`` (the
        iteration's convergence number) or extra keys mid-flight."""
        rec: dict[str, Any] = {"i": self.steps_total, "phases": {}, "metric": None}
        prev = self._step_rec
        self._step_rec = rec
        t0 = self._clock()
        try:
            with self.tracer.span(
                "train.step", kind="train", trainer=self.trainer,
                step=self.steps_total, **tags,
            ) as sp:
                yield rec
                sp.tags["metric"] = rec.get("metric")
        finally:
            rec["wall_s"] = round(self._clock() - t0, 6)
            rec["phases"] = {k: round(v, 6) for k, v in rec["phases"].items()}
            self._step_rec = prev
            self.steps_total += 1
            if len(self.timeline) < self.timeline_cap:
                self.timeline.append(rec)
            else:
                self.timeline_truncated = True
            if self.registry is not None:
                self._m_steps.inc(trainer=self.trainer)

    def add_rows(self, n: int) -> None:
        self.rows_total += int(n)
        if self.registry is not None:
            self._m_rows.inc(int(n), trainer=self.trainer)

    # -------------------------------------------------------- device time
    def _current_phase(self) -> str:
        return self._phase_stack[-1][0] if self._phase_stack else "unattributed"

    def note_device_time(self, seconds: float, where: str = "") -> None:
        """Attribute device/stall seconds to the current phase. Called by
        ``obs.jaxprof.timed_block_until_ready`` so sanctioned host-syncs
        anywhere inside a profiled train land in the profile."""
        seconds = max(0.0, seconds)
        self.device_s += seconds
        phase = self._current_phase()
        agg = self.phases.setdefault(phase, _PhaseAgg())
        agg.device_s += seconds
        if self._step_rec is not None:
            self._step_rec["device_s"] = round(
                self._step_rec.get("device_s", 0.0) + seconds, 6
            )
        if self.registry is not None:
            self._m_device.inc(seconds, trainer=self.trainer, phase=phase)

    def device_fetch(self, x: Any, where: str = "train") -> Any:
        """``np.asarray`` fetch with the stall accounted into the profile
        (the sanctioned form the ``train-unaccounted-sync`` lint demands)."""
        import numpy as np

        t0 = self._clock()
        out = np.asarray(x)
        self.note_device_time(self._clock() - t0, where)
        # sample while ``x`` is still referenced: for one-shot fetch paths
        # (fold-in solve, sharded final fetch) this is the only moment the
        # transient device arrays are observable as live
        self.sample_memory()
        return out

    def device_barrier(self, *arrays: Any, where: str = "train") -> float:
        """Wait for every array and return their checksum: one scalar
        summed on the device over all of them and fetched, so it exists
        only once they do (``ops.als.fetch_barrier`` is the unprofiled
        form). ``jax.block_until_ready`` is an equally true barrier on an
        attached chip (PERF.md, bring-up facts); this form stays because
        the checksum is the step's convergence metric — its deltas shrink
        as the factors converge. The stall is accounted to the current
        phase."""
        t0 = self._clock()
        try:
            import jax.numpy as jnp
            import numpy as np

            acc = None
            for a in arrays:
                s = jnp.sum(a, dtype=jnp.float32)
                acc = s if acc is None else acc + s
            # ONE fetch for the combined scalar: per-array fetches would
            # pay N device round trips each iteration and inflate the
            # recorded device time
            total = float(np.asarray(acc)) if acc is not None else 0.0
        except Exception:
            import jax

            jax.block_until_ready(arrays)
            total = 0.0
        self.note_device_time(self._clock() - t0, where)
        return total

    # -------------------------------------------------------------- memory
    def sample_memory(self) -> int:
        """Sample live-array bytes (global + busiest device) and device
        allocator stats; tracks peaks. Cheap enough to run per iteration."""
        total = live_array_bytes()
        if total > self.peak_live_bytes:
            self.peak_live_bytes = total
        per = live_bytes_per_device()
        busiest = max(per.values(), default=total)
        if busiest > self.peak_bytes_per_device:
            self.peak_bytes_per_device = busiest
            if self.registry is not None:
                self._m_peak.set(float(busiest), trainer=self.trainer)
        stats = device_memory_stats()
        if stats:
            self.device_memory_stats = stats
        devices = live_devices()
        if devices and (
            self.devices is None
            or devices["deviceCount"] > self.devices["deviceCount"]
        ):
            self.devices = devices
        return total

    def set_estimate(self, estimate: "CapacityEstimate") -> None:
        self.estimate = estimate
        if self.registry is not None:
            self._m_est.set(
                float(estimate.per_device_bytes), trainer=self.trainer
            )

    # -------------------------------------------------------------- finish
    def finish(self) -> "TrainProfile":
        """Close the profile: stop the clock, final memory sample, capture
        XLA compile totals. Idempotent."""
        if self.finished:
            return self
        self.pause()
        try:
            self.sample_memory()
        except Exception:  # noqa: BLE001 - memory evidence is best-effort
            pass
        ev, secs = monitoring_totals()
        self.xla_compiles = max(0, ev - self._xla0[0])
        self.xla_compile_s = max(0.0, secs - self._xla0[1])
        self.finished = True
        if self.registry is not None:
            for ph in self.phases:
                self._m_phase_g.set(0.0, trainer=self.trainer, phase=ph)
        return self

    def to_json_dict(self) -> dict[str, Any]:
        """The compact profile a ModelManifest carries (``pio models
        show`` renders it; ``diff`` compares wall + memory)."""
        wall = self.wall_s
        attributed = self.attributed_s
        return {
            "trainer": self.trainer,
            "wallClockS": round(wall, 6),
            "attributedS": round(attributed, 6),
            "deviceS": round(self.device_s, 6),
            # device seconds ÷ ATTRIBUTED wall — the PERF.md and
            # `pio top` definition; ÷ raw wall would read up to the 10%
            # tiling slack lower for the same train
            "deviceTimeFrac": (
                round(self.device_s / attributed, 4)
                if attributed > 0
                else (round(self.device_s / wall, 4) if wall > 0 else 0.0)
            ),
            "steps": self.steps_total,
            "rowsTotal": self.rows_total,
            "rowsPerS": round(self.rows_total / wall, 2) if wall > 0 else 0.0,
            "phases": {
                name: {
                    "count": agg.count,
                    "wallS": round(agg.wall_s, 6),
                    "deviceS": round(agg.device_s, 6),
                    "meanS": round(agg.wall_s / agg.count, 6) if agg.count else 0.0,
                }
                for name, agg in sorted(self.phases.items())
            },
            "timeline": self.timeline,
            "timelineTruncated": self.timeline_truncated,
            "memory": {
                "peakLiveBytes": self.peak_live_bytes,
                "peakBytesPerDevice": self.peak_bytes_per_device,
                "deviceStats": self.device_memory_stats,
            },
            "estimate": (
                self.estimate.to_json_dict() if self.estimate is not None else None
            ),
            # where the train's arrays sat (widest placement sampled)
            "device": self.devices,
            "xlaCompiles": self.xla_compiles,
            "xlaCompileS": round(self.xla_compile_s, 3),
        }


# ---------------------------------------------------------------------------
# current-profile plumbing (module-level helpers trainers call)
# ---------------------------------------------------------------------------

_CURRENT: contextvars.ContextVar[TrainProfile | None] = contextvars.ContextVar(
    "pio_train_profile", default=None
)


def current_profile() -> TrainProfile | None:
    prof = _CURRENT.get()
    if prof is not None and prof.finished:
        return None
    return prof


@contextlib.contextmanager
def use_profile(profile: TrainProfile) -> Iterator[TrainProfile]:
    token = _CURRENT.set(profile)
    try:
        yield profile
    finally:
        _CURRENT.reset(token)


@contextlib.contextmanager
def phase(name: str) -> Iterator[None]:
    """Module-level phase marker: records into the current profile, no-ops
    when nothing is profiling — trainer code stays unconditional."""
    prof = current_profile()
    if prof is None:
        yield
        return
    with prof.phase(name):
        yield


def device_fetch(x: Any, where: str = "train") -> Any:
    """Profiled ``np.asarray`` (plain fetch when nothing is recording)."""
    prof = current_profile()
    if prof is None:
        import numpy as np

        # pio-lint: disable=train-unaccounted-sync -- device_fetch IS the accounted fetch; unprofiled runs have no profile to account into
        return np.asarray(x)
    return prof.device_fetch(x, where)


# ---------------------------------------------------------------------------
# live-memory accounting
# ---------------------------------------------------------------------------


def _jax_backend_live() -> bool:
    """True only when jax is imported AND its backend is already
    initialized. ``jax.live_arrays()`` calls ``get_backend()``, which
    would *initialize* the backend — on a pure-host train (LocalAlgorithm
    engines) that means taking a chip that one process at a time may
    hold (a `pio deploy` beside this train, say) just to read a memory
    gauge. The samplers below therefore report 0/empty until some trainer
    actually touched a device (same contract as run_train's multi-host
    probe)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def live_array_bytes() -> int:
    """Total bytes of live jax arrays (global across shards); 0 without
    an initialized jax backend. The runtime cross-check for
    :func:`estimate_factors`."""
    if not _jax_backend_live():
        return 0
    import jax

    return sum(int(a.nbytes) for a in jax.live_arrays())


def live_bytes_per_device() -> dict[str, int]:
    """Live bytes per addressable device (replicated arrays count once per
    device they occupy — this is resident HBM, not logical size)."""
    if not _jax_backend_live():
        return {}
    import jax

    per: dict[str, int] = {}
    for a in jax.live_arrays():
        try:
            for sh in a.addressable_shards:
                key = str(sh.device)
                per[key] = per.get(key, 0) + int(sh.data.nbytes)
        except RuntimeError:  # deleted/donated between listing and reading
            continue
    return per


def live_devices() -> dict[str, Any] | None:
    """Where this process's live jax arrays sit: platform and kind of the
    devices holding them, how many devices hold any, and how many JAX
    sees. Read from the arrays, not from the environment — a train or a
    server reports the device its work actually used. None while no
    backend is initialized or no array is live (pure-host engines)."""
    if not _jax_backend_live():
        return None
    import jax

    held: dict[int, Any] = {}
    for a in jax.live_arrays():
        try:
            for d in a.devices():
                held[d.id] = d
        except RuntimeError:  # deleted/donated between listing and reading
            continue
    if not held:
        return None
    first = held[min(held)]
    return {
        "platform": first.platform,
        "deviceKind": first.device_kind,
        "deviceCount": len(held),
        "visibleDevices": jax.device_count(),
    }


def device_memory_stats() -> dict[str, Any] | None:
    """Allocator stats of the busiest device (``bytes_in_use`` /
    ``peak_bytes_in_use`` on TPU; the CPU backend returns None)."""
    if not _jax_backend_live():
        return None
    import jax

    best: dict[str, Any] | None = None
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            continue
        if best is None or stats.get("bytes_in_use", 0) > best["bytes_in_use"]:
            best = {
                "device": str(d),
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
            }
    return best


# ---------------------------------------------------------------------------
# capacity planner
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CapacityEstimate:
    """Predicted ALS training footprint. All byte fields are *model*
    numbers — what the formulation requires, cross-checkable against
    ``live_array_bytes()`` (the contract test holds the factor-table term
    to within 15% of measurement on the CPU backend).

    The model (mirrors ``ops/als.py`` structures; f32 accumulators):

    - ``factor_bytes``: both factor tables incl. the +1 dummy padding row
      — ``((users+1) + (items+1)) * k * bytes_per_elem``; a bf16
      ``gather_dtype`` adds a half-size copy of each table (the gather
      operand copy the solver keeps).
    - ``workspace_bytes``: the larger half-solve's normal-equation
      accumulators ``A [E,k,k] + b [E,k] + counts [E]`` at f32, plus ~4
      CG work vectors per system.
    - ``wire_bytes``: device-resident block tables for ``nnz`` ratings
      (cols int32 + vals f32 + mask int8 ≈ 9 B/slot, both sides) — 0 when
      ``nnz`` is unknown.
    - ``per_device_bytes``: everything row-sharded over ``n_devices``,
      PLUS one fully-gathered opposite factor table when sharded — the
      ALX schedule all-gathers the fixed side each half-solve, and that
      transient is exactly what OOMs first on a pod.
    """

    users: int
    items: int
    rank: int
    dtype: str
    gather_dtype: str
    n_devices: int
    nnz: int | None
    factor_bytes: int
    workspace_bytes: int
    wire_bytes: int
    total_bytes: int
    per_device_bytes: int

    def to_json_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def fits(self, hbm_bytes_per_device: int | float) -> bool:
        return self.per_device_bytes <= hbm_bytes_per_device


def _mesh_devices(mesh: Any) -> int:
    """Device count from a mesh spec: an int, a ``"data=8,model=2"``
    string, a ``{"data": 8}`` dict, a jax Mesh, or None (=1)."""
    if mesh is None:
        return 1
    if isinstance(mesh, int):
        return max(1, mesh)
    if isinstance(mesh, str):
        s = mesh.strip()
        if s.isdigit():  # bare device count: "--mesh 8"
            return max(1, int(s))
        n = 1
        for part in mesh.split(","):
            if not part.strip():
                continue
            _, sep, size = part.partition("=")
            if not sep or not size.strip():
                raise ValueError(
                    f"mesh axis {part!r} needs an explicit size for the "
                    f"capacity planner (e.g. 'data=8,model=2')"
                )
            v = int(size)
            if v <= 0:
                raise ValueError(
                    f"mesh axis sizes must be explicit positives for the "
                    f"capacity planner, got {part!r}"
                )
            n *= v
        return max(1, n)
    if isinstance(mesh, dict):
        n = 1
        for axis, v in mesh.items():
            v = int(v)
            if v <= 0:
                raise ValueError(
                    f"mesh axis {axis!r} size must be positive, got {v}"
                )
            n *= v
        return max(1, n)
    shape = getattr(mesh, "shape", None)  # jax Mesh duck-type
    if shape is not None:
        n = 1
        for v in dict(shape).values():
            n *= int(v)
        return max(1, n)
    raise TypeError(f"cannot derive a device count from mesh {mesh!r}")


def estimate_factors(
    users: int,
    items: int,
    k: int,
    dtype: str = "f32",
    mesh: Any = None,
    *,
    nnz: int | None = None,
    gather_dtype: str = "f32",
) -> CapacityEstimate:
    """Predict the per-device HBM footprint of an ALS train (see
    :class:`CapacityEstimate` for the model). Pure arithmetic — safe to
    call from ``pio doctor`` without a device in sight."""
    if users < 0 or items < 0 or k <= 0:
        raise ValueError(f"need users/items >= 0 and k > 0, got {users}/{items}/{k}")
    bpe = 2 if dtype == "bf16" else 4
    n_dev = _mesh_devices(mesh)
    user_table = (users + 1) * k * bpe
    item_table = (items + 1) * k * bpe
    factor = user_table + item_table
    if gather_dtype == "bf16":
        factor += (user_table + item_table) // 2  # bf16 gather copies
    e = max(users, items) + 1
    workspace = e * (k * k + k + 1) * 4 + 4 * e * k * 4
    wire = 2 * int(nnz) * 9 if nnz else 0
    total = factor + workspace + wire
    per_device = -(-total // n_dev)
    if n_dev > 1:
        # the gathered opposite side is resident in full on every device
        # during a half-solve — add the larger table once
        per_device += max(user_table, item_table)
    return CapacityEstimate(
        users=users,
        items=items,
        rank=k,
        dtype=dtype,
        gather_dtype=gather_dtype,
        n_devices=n_dev,
        nnz=nnz,
        factor_bytes=factor,
        workspace_bytes=workspace,
        wire_bytes=wire,
        total_bytes=total,
        per_device_bytes=int(per_device),
    )


def estimate_ann(
    items: int,
    dim: int,
    clusters: int = 0,
    nprobe: int = 0,
    *,
    quantize_int8: bool = False,
    batch: int = 64,
) -> dict[str, Any]:
    """Price a pinned ANN index's serving HBM next to the factor tables
    (``pio doctor --capacity ... --ann "clusters,nprobe"``; docs/ann.md).

    Model (mirrors ``ann/index.py``'s layout):

    - centroids ``[C, dim]`` f32;
    - bucket ids ``[C, cap]`` int32 + bucket vectors ``[C, cap, dim]``
      (f32, or int8 + a per-item f32 scale when quantized), with ``cap``
      the build's own capacity rule (``ann.index.bucket_capacity``: pow2
      of 2x the balanced mean — overflow spills to neighbor clusters
      instead of inflating every bucket);
    - a per-batch search transient: the gathered probe slabs
      ``[batch, nprobe, cap, dim]`` plus their score matrix — the term
      that actually bounds ``batch * nprobe``.

    The index is replicated per serving device (it answers point queries,
    it is not sharded), so every byte here is a per-device byte.
    """
    if items <= 0 or dim <= 0:
        raise ValueError(f"need items > 0 and dim > 0, got {items}/{dim}")
    from predictionio_tpu.ann.index import (
        bucket_capacity,
        default_clusters,
        default_nprobe,
    )

    clusters = clusters or default_clusters(items)
    clusters = max(1, min(clusters, items))
    nprobe = min(nprobe or default_nprobe(clusters), clusters)
    # the build's own capacity rule — estimate and artifact agree exactly
    cap = bucket_capacity(items, clusters)
    vec_elem = 1 if quantize_int8 else 4
    centroid_bytes = clusters * dim * 4
    bucket_bytes = clusters * cap * (dim * vec_elem + 4)
    if quantize_int8:
        bucket_bytes += clusters * cap * 4  # per-item f32 scales
    search_transient = batch * nprobe * cap * (dim * 4 + 8)
    total = centroid_bytes + bucket_bytes
    return {
        "items": items,
        "dim": dim,
        "clusters": clusters,
        "nprobe": nprobe,
        "bucketCap": cap,
        "quantized": quantize_int8,
        "centroidBytes": centroid_bytes,
        "bucketBytes": bucket_bytes,
        "searchTransientBytes": search_transient,
        "perDeviceBytes": total,
        "candidatesPerQuery": nprobe * cap,
        "candidateFrac": round(min(1.0, nprobe * cap / max(1, items)), 4),
    }


# ---------------------------------------------------------------------------
# sharding inspector
# ---------------------------------------------------------------------------

# HLO/StableHLO spellings of the cross-device collectives worth counting
_COLLECTIVES = (
    ("all_gather", ("all-gather", "all_gather")),
    ("all_reduce", ("all-reduce", "all_reduce")),
    ("reduce_scatter", ("reduce-scatter", "reduce_scatter")),
    ("collective_permute", ("collective-permute", "collective_permute")),
    ("all_to_all", ("all-to-all", "all_to_all")),
)


def count_collectives(hlo_text: str) -> dict[str, int]:
    """Count collective ops in a lowered/compiled module's text. Applied
    to the *compiled* (post-GSPMD) HLO this is the ground truth for "did
    the partitioner insert an all-gather I didn't plan"."""
    out: dict[str, int] = {}
    for name, spellings in _COLLECTIVES:
        n = 0
        for line in hlo_text.splitlines():
            # count op sites, not attribute mentions: an op line names the
            # op right after " = " (HLO) or as a stablehlo.<op> call. TPU
            # optimized HLO emits async pairs — count the -start op (the
            # matching -done carries no second collective)
            for sp in spellings:
                if (
                    f"= {sp}" in line
                    or f" {sp}(" in line
                    or f" {sp}-start(" in line
                    or f".{sp}" in line
                ):
                    n += 1
                    break
        if n:
            out[name] = n
    return out


def describe_shardings(tree: Any, prefix: str = "") -> list[dict[str, Any]]:
    """Flatten a pytree of jax arrays into placement records:
    ``{"name", "shape", "dtype", "bytes", "sharding", "devices",
    "replicated", "per_device_bytes"}``. ``replicated`` is only flagged
    when the array actually spans multiple devices — a single-device
    array is trivially "replicated" and would drown the signal."""
    import jax

    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    out: list[dict[str, Any]] = []
    for path, leaf in leaves_with_paths:
        if not hasattr(leaf, "sharding") or not hasattr(leaf, "nbytes"):
            continue
        name = prefix + jax.tree_util.keystr(path)
        sharding = leaf.sharding
        devices = len(getattr(sharding, "device_set", ()) or ()) or 1
        replicated = bool(
            devices > 1 and getattr(sharding, "is_fully_replicated", False)
        )
        nbytes = int(leaf.nbytes)
        per_device = nbytes if replicated else -(-nbytes // devices)
        spec = getattr(sharding, "spec", None)
        out.append(
            {
                "name": name or "<root>",
                "shape": list(leaf.shape),
                "dtype": str(leaf.dtype),
                "bytes": nbytes,
                "sharding": str(spec) if spec is not None else str(sharding),
                "devices": devices,
                "replicated": replicated,
                "per_device_bytes": per_device,
            }
        )
    return out


def find_replicated(
    entries: list[dict[str, Any]], min_bytes: int = 1 << 20
) -> list[dict[str, Any]]:
    """The flag list: fully-replicated arrays at or above ``min_bytes`` —
    on a pod these are per-device HBM spent on every chip for data that
    could be sharded."""
    return [
        e
        for e in entries
        if e.get("replicated") and e.get("bytes", 0) >= min_bytes
    ]


def inspect_train_step(
    jitted_fn: Any,
    *args: Any,
    replicated_min_bytes: int = 1 << 20,
    arg_names: tuple[str, ...] | None = None,
) -> dict[str, Any]:
    """One-stop inspector for a pjit'd train step: lower+compile (without
    executing — safe before a donating call), count post-partitioning
    collectives, and describe every input's placement. The dryrun embeds
    this report in ``MULTICHIP_r*.json``.

    Cost note: the AOT ``lower().compile()`` here does NOT seed the jit
    dispatch cache, so a caller that later invokes ``jitted_fn`` directly
    compiles the program a second time. Deliberate for a preflight
    inspector (tiny dryrun shapes, and the report must exist even if the
    step is never executed) — don't call this around a production train
    step you're about to run."""
    report: dict[str, Any] = {"collectives": {}, "arrays": [], "flags": []}
    try:
        lowered = jitted_fn.lower(*args)
        try:
            text = lowered.compile().as_text()
        except Exception:  # noqa: BLE001 - backends without HLO dumping
            text = lowered.as_text()
        report["collectives"] = count_collectives(text)
    except Exception as exc:  # noqa: BLE001 - inspection must not kill a train
        report["error"] = f"{type(exc).__name__}: {exc}"
    arrays: list[dict[str, Any]] = []
    for i, a in enumerate(args):
        name = arg_names[i] if arg_names and i < len(arg_names) else f"arg{i}"
        arrays.extend(describe_shardings(a, prefix=name))
    report["arrays"] = arrays
    for e in find_replicated(arrays, replicated_min_bytes):
        report["flags"].append(
            f"fully-replicated {e['bytes']} B array {e['name']} on "
            f"{e['devices']} devices — shard it or accept the per-chip cost"
        )
    return report


__all__ = [
    "TRAIN_PHASES",
    "PHASE_HOST_ETL",
    "PHASE_SWEEP",
    "PHASE_SOLVE",
    "PHASE_EVAL",
    "CapacityEstimate",
    "TrainProfile",
    "count_collectives",
    "current_profile",
    "describe_shardings",
    "device_fetch",
    "device_memory_stats",
    "estimate_ann",
    "estimate_factors",
    "find_replicated",
    "inspect_train_step",
    "live_array_bytes",
    "live_bytes_per_device",
    "phase",
    "register_train_metrics",
    "use_profile",
]
