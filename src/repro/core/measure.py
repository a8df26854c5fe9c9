"""Honest model↔measurement loop: timing, calibration, measurement cache.

The paper's workflow only means something when the analytic (n, m) model
is compared against *measured* performance of the platform actually
running (docs/pipeline.md §measure, DESIGN.md §9). Off-TPU the Pallas
kernels execute under the interpreter at host speed, so diffing them
against the TPU-v5e roofline produced ``rel_error ≈ 0.9999`` on every
point — numerically meaningless. This module makes the loop honest,
in three pieces:

1. **Timing harness** — :func:`time_run`: warm-up calls are separated
   from measured reps (compile/trace time never pollutes the sample),
   *every* rep is synchronized with ``jax.block_until_ready`` (JAX
   dispatch is async; blocking only the last rep under-counts wall
   time), the reported wall time is the median of the reps (robust to
   scheduler noise), and the timer's own overhead — measured from
   back-to-back ``perf_counter`` pairs — is subtracted.

2. **Backend calibration** — micro-benchmarks measure the live
   platform's effective elementwise f32 throughput
   (:func:`measure_elementwise_gflops`, a generated FMA-chain SPD core
   run through the real §codegen kernel path) and memory bandwidth
   (:func:`measure_memory_bandwidth_gbs`), producing a
   :class:`BackendCalibration` whose :meth:`~BackendCalibration.target`
   is a :class:`~repro.core.dse.TPUTarget` with *measured* constants.
   :func:`calibrate_execution` anchors the compute constant through the
   same ``run_factory`` the explorer times (the honest form: interpreter
   throughput on CPU, chip throughput on TPU), over a small probe set
   spanning the lattice's fused-step range (:data:`PROBE_PLANS`), so
   predicted-vs-measured becomes a real model-fidelity signal — the
   model must still predict how performance moves across the
   (block_h, m, d) lattice from those anchors.

3. **Measurement cache** — :class:`MeasurementCache`: a persistent
   on-disk store keyed by (core fingerprint, grid shape, run plan,
   backend, interpret, reps, warmup), so repeated sweeps and benchmark
   runs skip recompile+retime. :func:`core_fingerprint` derives a
   stable content hash from the SPD core's DFG structure; a changed
   core, plan, or backend is a changed key, never a stale hit.

``Explorer.execute_frontier`` threads all three (docs/pipeline.md
§execute): it times every frontier point through :func:`measured_run`
and reports rel_error against the calibrated prediction.
"""

from __future__ import annotations

import hashlib
import json
import os
import statistics
import time
from dataclasses import dataclass, field, replace
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from repro.compat import resolve_interpret

from .dse import StreamWorkload, TPUModel, TPUTarget
from .legalize import blocking_plan, lane_multiple

__all__ = [
    "BackendCalibration",
    "MeasurementCache",
    "PROBE_PLANS",
    "Timing",
    "calibrate_backend",
    "calibrate_execution",
    "code_salt",
    "core_fingerprint",
    "default_cache_path",
    "measure_elementwise_gflops",
    "measure_memory_bandwidth_gbs",
    "measured_run",
    "resolve_cache",
    "time_run",
    "timer_overhead",
]


# --------------------------------------------------------------------------
# Timing harness
# --------------------------------------------------------------------------


def timer_overhead(samples: int = 64) -> float:
    """Median cost of one timed-region bracket (two ``perf_counter`` calls).

    Subtracted from every measured rep so sub-millisecond kernels are not
    inflated by the clock itself.
    """
    deltas = []
    for _ in range(max(8, samples)):
        t0 = time.perf_counter()
        t1 = time.perf_counter()
        deltas.append(t1 - t0)
    return statistics.median(deltas)


@dataclass(frozen=True)
class Timing:
    """One timed measurement: median-of-reps wall time plus the raw sample."""

    wall_s: float  # median per-rep wall time, timer overhead subtracted
    times_s: tuple  # every measured rep (post-subtraction), in order
    reps: int
    warmup: int
    overhead_s: float  # per-bracket timer overhead that was subtracted

    @property
    def total_s(self) -> float:
        return float(sum(self.times_s))


def time_run(
    fn: Callable[[], object],
    *,
    reps: int = 3,
    warmup: int = 1,
    block: Callable | None = None,
) -> Timing:
    """Time ``fn`` honestly: warm up, block every rep, take the median.

    * ``warmup`` un-timed calls run (and are blocked) first, so
      compilation/tracing never lands in the measured sample;
    * each of the ``reps`` measured calls is individually synchronized
      with ``block`` (default ``jax.block_until_ready``) *inside* its
      timed region — JAX dispatch is asynchronous, and blocking only the
      final dispatch lets reps overlap and under-counts wall time;
    * the reported ``wall_s`` is the median rep, with the timer's own
      bracket overhead (:func:`timer_overhead`) subtracted and the
      result floored at 1 ns so downstream rates stay finite.
    """
    if reps < 1:
        raise ValueError(f"reps must be >= 1, got {reps}")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0, got {warmup}")
    if block is None:
        block = jax.block_until_ready
    for _ in range(warmup):
        block(fn())
    overhead = timer_overhead()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        block(fn())
        t1 = time.perf_counter()
        times.append(max(t1 - t0 - overhead, 1e-9))
    return Timing(
        wall_s=max(statistics.median(times), 1e-9),
        times_s=tuple(times),
        reps=reps,
        warmup=warmup,
        overhead_s=overhead,
    )


# --------------------------------------------------------------------------
# Core fingerprints (cache keys that survive process restarts)
# --------------------------------------------------------------------------


def _core_struct(core) -> dict:
    """A canonical, JSON-stable description of a DFG ``Core``."""
    return {
        "name": core.name,
        "main_in": [list(i.ports) for i in core.main_in],
        "main_out": [list(i.ports) for i in core.main_out],
        "brch_in": [list(i.ports) for i in core.brch_in],
        "brch_out": [list(i.ports) for i in core.brch_out],
        "regs": list(core.regs),
        "params": {k: float(v) for k, v in sorted(core.params.items())},
        "drcts": [[list(d), list(s)] for d, s in core.drcts],
        "nodes": [
            [
                n.name,
                n.kind,
                list(n.inputs),
                list(n.outputs),
                repr(n.expr),
                n.module,
                n.delay,
                list(n.params),
            ]
            for n in core.nodes
        ],
    }


def backend_descriptor() -> str:
    """Cache-key identity of the live platform: backend *and* device kind.

    ``jax.default_backend()`` alone says only "cpu"/"tpu" — two TPU
    generations (or two different machines sharing a cache directory)
    would alias onto one key and serve each other's timings.
    """
    kind = "?"
    try:
        devs = jax.devices()
        if devs:
            kind = getattr(devs[0], "device_kind", "?") or "?"
    except RuntimeError:  # no backend initialized: keep the bare name
        pass
    return f"{jax.default_backend()}/{kind}"


def core_fingerprint(obj) -> str:
    """Stable content hash of an SPD core (any pipeline stage of it).

    Accepts a ``StreamKernel``, ``CompiledCore``, DFG ``Core``, or a
    plain string tag (for hand-written back ends with no SPD source,
    e.g. ``lbm_stream``). Two structurally identical cores fingerprint
    identically across processes; any change to the graph changes the
    key, so the measurement cache can never serve a stale core's time.
    """
    if isinstance(obj, str):
        return "tag:" + obj
    compiled = getattr(obj, "compiled", obj)  # StreamKernel -> CompiledCore
    core = getattr(compiled, "core", compiled)  # CompiledCore -> Core
    blob = json.dumps(_core_struct(core), sort_keys=True).encode()
    return "spd:" + hashlib.sha256(blob).hexdigest()[:16]


# --------------------------------------------------------------------------
# Persistent measurement cache
# --------------------------------------------------------------------------


def default_cache_path() -> str:
    """``$REPRO_MEASURE_CACHE`` or ``~/.cache/repro/measure-cache.json``."""
    env = os.environ.get("REPRO_MEASURE_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "measure-cache.json"
    )


#: Source files whose implementation determines a measurement's wall
#: time even when the SPD core's DFG (the fingerprint) is unchanged:
#: the kernel launchers and the stripe/shard lowerings.
_SALT_MODULES = (
    # the harness itself: what a rep includes and what a record stores
    "repro.core.measure",
    # graph evaluation: the per-element work the kernels execute
    "repro.core.compiler",
    "repro.core.dfg",
    "repro.core.library",
    # stripe lowering + launches
    "repro.core.codegen",
    "repro.core.program",
    "repro.core.distribute",
    "repro.kernels.spd_stream.streaming",
    "repro.kernels.spd_stream.ops",
    "repro.kernels.lbm_stream.lbm_stream",
    "repro.kernels.lbm_stream.ops",
)

_CODE_SALT: list[str] = []  # computed once per process


def code_salt() -> str:
    """Hash of the jax version + kernel-implementation sources.

    Folded into every cache key: a kernel optimization or a jax upgrade
    changes measured wall times without changing any core's DFG, so it
    must invalidate the cache — otherwise the trajectory file would
    silently record the *old* platform's timings as fresh measurements.
    """
    if not _CODE_SALT:
        h = hashlib.sha256()
        h.update(jax.__version__.encode())
        import importlib.util

        for mod in _SALT_MODULES:
            try:
                spec = importlib.util.find_spec(mod)
                if spec and spec.origin:
                    with open(spec.origin, "rb") as fh:
                        h.update(fh.read())
            except (ImportError, OSError):  # absent module: salt w/o it
                h.update(mod.encode())
        _CODE_SALT.append(h.hexdigest()[:12])
    return _CODE_SALT[0]


class MeasurementCache:
    """On-disk store of timed measurements, keyed by what determines them.

    A key is the SHA-256 of (core fingerprint, grid shape, run plan
    ``(block_h, m, steps, d, double_buffer)``, backend, interpret, reps,
    warmup) plus
    the :func:`code_salt` — the jax version and the kernel
    implementation sources — so neither a changed core *nor* a changed
    kernel/runtime can ever serve a stale timing (see :meth:`make_key`).
    Values are the :class:`Timing` facts plus the human-readable key
    fields, so the cache file doubles as a measurement log. Writes are
    atomic (temp file + ``os.replace``) and re-merge the on-disk state
    first, so concurrent benchmark runs do not clobber each other's
    entries. ``hits``/``misses`` count this process's lookups (reported
    by ``benchmarks/dse_sweep.py``).
    """

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = os.fspath(path) if path is not None else default_cache_path()
        self.hits = 0
        self.misses = 0
        self._data: dict[str, dict] = self._load()

    # ---- keys --------------------------------------------------------------

    @staticmethod
    def make_key(
        fingerprint: str,
        grid_shape: Sequence[int],
        plan: Sequence[int],
        backend: str,
        interpret: bool,
        reps: int,
        warmup: int,
    ) -> str:
        """Deterministic key over everything a measurement depends on."""
        fields = {
            "fingerprint": fingerprint,
            "grid_shape": [int(v) for v in grid_shape],
            # (block_h, m, steps, d[, db, b[, fusion]]) — the trailing
            # fusion spec is a string (docs/pipeline.md §program)
            "plan": [v if isinstance(v, str) else int(v) for v in plan],
            "backend": backend,
            "interpret": bool(interpret),
            "reps": int(reps),
            "warmup": int(warmup),
            "code": code_salt(),  # kernel sources + jax version
        }
        blob = json.dumps(fields, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:24]

    # ---- lookups -----------------------------------------------------------

    def get(self, key: str) -> dict | None:
        rec = self._data.get(key)
        if rec is None:
            self.misses += 1
        else:
            self.hits += 1
        return rec

    def peek(self, key: str) -> dict | None:
        """Like :meth:`get` but without touching the hit/miss counters.

        Surrogate search strategies (docs/pipeline.md §study) scan every
        candidate's key to warm-start from prior measurements; those
        scans are bookkeeping, not lookups, and must not distort the
        stats the benchmarks report.
        """
        return self._data.get(key)

    def put(self, key: str, record: dict) -> None:
        self._data[key] = dict(record)
        self._flush()

    def stats(self) -> dict:
        return {
            "path": self.path,
            "entries": len(self._data),
            "hits": self.hits,
            "misses": self.misses,
        }

    def __len__(self) -> int:
        return len(self._data)

    # ---- persistence -------------------------------------------------------

    def _load(self) -> dict[str, dict]:
        try:
            with open(self.path, encoding="utf-8") as fh:
                data = json.load(fh)
            return data if isinstance(data, dict) else {}
        except (OSError, ValueError):
            return {}

    def _flush(self) -> None:
        # Full re-load + rewrite per put() is deliberate: a measurement
        # costs seconds, a rewrite of this file costs well under a
        # millisecond at realistic cache sizes, and flushing eagerly
        # means a crashed or interrupted sweep keeps everything it paid
        # for while concurrent runs merge instead of clobbering.
        directory = os.path.dirname(self.path) or "."
        os.makedirs(directory, exist_ok=True)
        # Serialize the load→merge→replace against concurrent writers: two
        # processes flushing between each other's load and replace would
        # otherwise drop whichever record landed in the window. Study
        # resume (docs/pipeline.md §study) leans on this contract, so it
        # is a lock, not a race we tolerate. Best-effort: platforms or
        # filesystems without flock fall back to the unlocked merge.
        lock_fh = None
        try:
            import fcntl

            lock_fh = open(f"{self.path}.lock", "w")
            fcntl.flock(lock_fh, fcntl.LOCK_EX)
        except (ImportError, OSError):
            lock_fh = None
        try:
            merged = self._load()  # re-merge concurrent writers, newest wins
            merged.update(self._data)
            self._data = merged
            tmp = f"{self.path}.tmp.{os.getpid()}"
            try:
                with open(tmp, "w", encoding="utf-8") as fh:
                    json.dump(merged, fh, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            except OSError:
                # A read-only cache location must never fail the measurement.
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        finally:
            if lock_fh is not None:
                try:
                    import fcntl

                    fcntl.flock(lock_fh, fcntl.LOCK_UN)
                except (ImportError, OSError):
                    pass
                lock_fh.close()


def resolve_cache(policy) -> MeasurementCache | None:
    """Normalize an ``execute_frontier`` cache policy argument.

    ``None``/``False`` → no caching; ``True`` → the default on-disk
    cache (:func:`default_cache_path`); a path → a cache at that path;
    a :class:`MeasurementCache` → itself (lets callers read hit/miss
    stats afterwards).
    """
    if policy is None or policy is False:
        return None
    if policy is True:
        return MeasurementCache()
    if isinstance(policy, MeasurementCache):
        return policy
    return MeasurementCache(policy)


def measured_run(
    run: Callable[[], object],
    *,
    key: str | None = None,
    cache: MeasurementCache | None = None,
    reps: int = 3,
    warmup: int = 1,
) -> tuple[float, bool]:
    """Time ``run`` through the cache: ``(wall_s, came_from_cache)``.

    With a cache and a key, a prior measurement under the identical key
    is returned without recompiling or retiming; otherwise the run is
    timed with :func:`time_run` and the result stored.
    """
    if cache is not None and key is not None:
        rec = cache.get(key)
        if rec is not None:
            return float(rec["wall_s"]), True
    timing = time_run(run, reps=reps, warmup=warmup)
    if cache is not None and key is not None:
        cache.put(
            key,
            {
                "wall_s": timing.wall_s,
                "times_s": list(timing.times_s),
                "reps": timing.reps,
                "warmup": timing.warmup,
                "overhead_s": timing.overhead_s,
            },
        )
    return timing.wall_s, False


# --------------------------------------------------------------------------
# Backend calibration
# --------------------------------------------------------------------------


#: Per-process memo of bandwidth probes, keyed by (backend, mbytes,
#: reps, warmup): platform bandwidth does not drift within one process,
#: and re-probing on every calibrated execute_frontier call would pay a
#: fresh jit + timed passes each time.
_MEM_PROBE_MEMO: dict[tuple, float] = {}


def measure_memory_bandwidth_gbs(
    mbytes: int = 32, *, reps: int = 3, warmup: int = 1, memo: bool = True
) -> float:
    """Effective f32 streaming bandwidth (GB/s) of the live backend.

    Times a jitted elementwise pass over an ``mbytes`` f32 buffer — one
    read + one write per element, the same traffic shape as a stream
    kernel's HBM round-trip — and reports moved bytes / median wall.
    Memoized per process (pass ``memo=False`` to force a fresh probe);
    deliberately *not* persisted to the on-disk measurement cache, so
    every session re-measures the platform it actually has.
    """
    key = (jax.default_backend(), mbytes, reps, warmup)
    if memo and key in _MEM_PROBE_MEMO:
        return _MEM_PROBE_MEMO[key]
    n = max(1, (mbytes * 2**20) // 4)
    x = jnp.full((n,), 1.5, jnp.float32)
    f = jax.jit(lambda a: a + 1.0)
    timing = time_run(lambda: f(x), reps=reps, warmup=warmup)
    bw = 2 * n * 4 / timing.wall_s / 1e9
    if memo:
        _MEM_PROBE_MEMO[key] = bw
    return bw


def _fma_chain_spd(chain: int) -> str:
    """SPD source of a ``chain``-deep FMA pipeline (2·chain flops/elem)."""
    lines = [
        "Name CalibChain;",
        "Main_In {mi::u};",
        "Main_Out {mo::v};",
        "Append_Reg {rg::a};",
    ]
    prev = "u"
    for i in range(chain):
        out = "v" if i == chain - 1 else f"t{i}"
        lines.append(f"EQU N{i}, {out} = {prev}*a + 0.125;")
        prev = out
    return "\n".join(lines)


def measure_elementwise_gflops(
    interpret: bool | None = None,
    *,
    chain: int = 32,
    shape: tuple[int, int] = (128, 128),
    m: int = 1,
    block_h: int = 32,
    reps: int = 3,
    warmup: int = 1,
) -> float:
    """Effective elementwise f32 throughput (GFLOP/s) of the live backend.

    Compiles a generated ``chain``-deep FMA SPD core through the real
    §codegen path and times its temporal-blocking Pallas launch in the
    requested mode — so the number reflects the execution path the
    explorer actually measures (the Pallas interpreter on CPU, the
    compiled kernel on TPU), not a synthetic numpy loop.
    """
    from .compiler import Registry
    from .spd import parse_spd

    h, w = shape
    kern = Registry().compile(parse_spd(_fma_chain_spd(chain))).stream_kernel()
    state = jnp.full((1, h, w), 0.5, jnp.float32)
    bh, mm, _ = blocking_plan(h, block_h, m, halo=kern.halo, width=w, words=1,
                              interpret=interpret)
    timing = time_run(
        lambda: kern.run_blocked(
            state, (0.997,), steps=mm, m=mm, block_h=bh, interpret=interpret
        ),
        reps=reps,
        warmup=warmup,
    )
    flops = h * w * mm * 2 * chain  # halo = 0: no recompute term
    return flops / timing.wall_s / 1e9


@dataclass(frozen=True)
class BackendCalibration:
    """Measured constants of the platform actually running.

    ``elem_gflops`` / ``mem_gbs`` are the single-device effective
    elementwise f32 throughput and memory bandwidth; ``by_d`` optionally
    carries measured *aggregate* throughput per device-axis value (on a
    host with forced devices, d "chips" share one CPU, so aggregate
    throughput is measured, not assumed d-linear). :meth:`target` folds
    the measurements into a :class:`~repro.core.dse.TPUTarget`, which
    :meth:`repro.core.dse.TPUModel.calibrated` wraps into a model — the
    calibrated side of the predicted-vs-measured diff
    (docs/pipeline.md §measure).
    """

    backend: str
    interpret: bool
    elem_gflops: float
    mem_gbs: float
    by_d: tuple = ()  # ((d, aggregate_gflops), ...)
    detail: Mapping = field(default_factory=dict)

    def gflops(self, d: int = 1) -> float:
        """Measured aggregate throughput across ``d`` devices.

        Falls back to the single-device figure when ``d`` was not probed
        — deliberately conservative: unprobed scaling is not assumed.
        """
        return float(dict(self.by_d).get(int(d), self.elem_gflops))

    def target(self, d: int = 1, base: TPUTarget | None = None) -> TPUTarget:
        """A :class:`TPUTarget` carrying this calibration's constants.

        Per-chip compute is aggregate/d so the model's ``× d`` scaling
        reproduces the *measured* aggregate for that device count.
        Bandwidth divides by ``d`` only when the "devices" share one
        host memory system (CPU backend / interpret mode — forced host
        devices split one machine's bandwidth); on real accelerators
        the probe measured a single chip's HBM and every chip has its
        own, so the per-chip constant stands. The target's lane rule is
        the one of the mode measured (``legalize.lane_multiple``).
        """
        base = base or TPUTarget()
        d = max(1, int(d))
        mode = ":interpret" if self.interpret else ""
        shared_memory = self.interpret or self.backend == "cpu"
        return replace(
            base,
            name=f"{base.name}+measured[{self.backend}{mode}]",
            vpu_f32_tflops=self.gflops(d) / d / 1e3,
            hbm_gbs=self.mem_gbs / d if shared_memory else self.mem_gbs,
            lanes=lane_multiple(self.interpret),
        )

    def model(self, d: int = 1, base: TPUTarget | None = None) -> TPUModel:
        """Shorthand for ``TPUModel.calibrated(self, d=d, base=base)``."""
        return TPUModel.calibrated(self, d=d, base=base)


def calibrate_backend(
    interpret: bool | None = None,
    *,
    chain: int = 32,
    shape: tuple[int, int] = (128, 128),
    mem_mbytes: int = 32,
    reps: int = 3,
    warmup: int = 1,
) -> BackendCalibration:
    """Generic platform calibration from the two micro-benchmarks.

    The compute constant comes from the FMA-chain probe kernel
    (:func:`measure_elementwise_gflops`), the bandwidth constant from
    :func:`measure_memory_bandwidth_gbs` — no application core needed.
    For per-kernel anchoring inside the explorer's measurement loop use
    :func:`calibrate_execution`.
    """
    interpret = resolve_interpret(interpret)
    gflops = measure_elementwise_gflops(
        interpret, chain=chain, shape=shape, reps=reps, warmup=warmup
    )
    mem = measure_memory_bandwidth_gbs(mem_mbytes, reps=reps, warmup=warmup)
    return BackendCalibration(
        backend=jax.default_backend(),
        interpret=interpret,
        elem_gflops=gflops,
        mem_gbs=mem,
        by_d=((1, gflops),),
        detail={"chain": chain, "shape": list(shape), "mem_mbytes": mem_mbytes},
    )


#: Default calibration probe set, as (block_h, m) pairs. Two anchors
#: spanning the lattice's fused-step range: interpret-mode cost has a
#: per-launch/per-application overhead component the roofline does not
#: model, so a single anchor at one m systematically mis-prices points
#: at another. Each probe legalizes like any frontier point; the
#: anchors' geometric mean becomes the platform's effective throughput.
PROBE_PLANS: tuple = ((16, 4), (64, 8))


def calibrate_execution(
    run_factory: Callable,
    *,
    workload: StreamWorkload,
    grid_shape: tuple[int, int],
    halo: int | None = None,
    width: int = 0,
    words: int = 0,
    d_values: Sequence[int] = (1,),
    probe_plans: Sequence[tuple[int, int]] = PROBE_PLANS,
    interpret: bool | None = None,
    reps: int = 3,
    warmup: int = 1,
    cache: MeasurementCache | None = None,
    fingerprint: str | None = None,
    mem_gbs: float | None = None,
) -> BackendCalibration:
    """Anchor the compute constant through the *actual* execution path.

    Runs a small probe set — ``probe_plans`` as (block_h, m) requests,
    each legalized exactly like a frontier point (duplicates after
    legalization collapse) — through the same ``run_factory`` the
    explorer times, per requested device-axis value, and backs the
    platform's effective elementwise throughput out of the wall times
    (counting halo-recomputed sites: that is work the backend really
    performed; the anchor is the geometric mean over the probe set).
    The model then has to predict every frontier point from these
    anchors, which is what makes the reported rel_error a model-fidelity
    signal rather than a host-vs-TPU speed ratio
    (docs/pipeline.md §measure).

    Probe measurements go through the same :class:`MeasurementCache`
    key space as frontier runs, so repeated sweeps skip re-calibration
    and a probe plan that legalizes onto a frontier point's plan reuses
    its timing outright.
    """
    interpret = resolve_interpret(interpret)
    h, w = grid_shape
    halo = workload.halo if halo is None else halo
    backend = backend_descriptor()
    by_d = []
    for d in d_values:
        d = int(d)
        plans = []
        for req_bh, req_m in probe_plans:
            try:
                bh, m, db = blocking_plan(
                    h, req_bh, req_m, halo=halo, width=width, words=words,
                    d=d, interpret=interpret,
                )
            except ValueError:
                continue  # this anchor has no legal plan here (e.g. a
                #           VMEM-tight grid); the others still calibrate
            if (bh, m, db) not in plans:
                plans.append((bh, m, db))
        rates = []
        for bh, m, db in plans:
            nsteps = m
            try:
                run = run_factory(nsteps, m, bh, d, db)
            except TypeError:  # legacy 4-arg factories predate the knob
                run = run_factory(nsteps, m, bh, d)
            if run is None:
                continue
            # Same key space as frontier runs: (fingerprint, grid,
            # plan, ...) fully determine a measurement, so a probe plan
            # that coincides with a frontier point shares its timing
            # (no duplicate compile+retime on a cold run).
            key = None
            if cache is not None and fingerprint is not None:
                key = MeasurementCache.make_key(
                    fingerprint, (h, w), (bh, m, nsteps, d, int(db)),
                    backend, interpret, reps, warmup,
                )
            wall, _ = measured_run(
                run, key=key, cache=cache, reps=reps, warmup=warmup
            )
            useful = bh / (bh + 2 * m * halo) if halo else 1.0
            computed_flops = h * w * nsteps * workload.flops_per_elem / useful
            rates.append(computed_flops / wall / 1e9)
        if rates:
            by_d.append((d, float(statistics.geometric_mean(rates))))
    if not by_d:
        raise ValueError(
            "calibrate_execution: run_factory produced no runnable probe "
            f"for any d in {tuple(d_values)}"
        )
    if mem_gbs is None:
        mem_gbs = measure_memory_bandwidth_gbs(reps=reps, warmup=warmup)
    anchor = dict(by_d)
    return BackendCalibration(
        backend=backend,
        interpret=interpret,
        elem_gflops=anchor.get(1, by_d[0][1]),
        mem_gbs=float(mem_gbs),
        by_d=tuple(by_d),
        detail={
            "probe_plans": [list(p) for p in probe_plans],
            "grid_shape": [h, w],
            "flops_per_elem": workload.flops_per_elem,
        },
    )
