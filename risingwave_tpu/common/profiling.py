"""Device profiling plane: per-dispatch cost/memory telemetry.

Counterpart of the reference's compute-node profiling surface
(reference: src/compute/src/rpc/service/monitor_service.rs profiling
handlers + src/common/src/estimate_size/ feeding eviction decisions).
The TPU-native variant is XLA-shaped: the unit of work is a *dispatch*
(one jitted epoch callable entering XLA), so the plane hangs off the
same qualnames ``common/dispatch_count.py`` and the
``EPOCH_BUILDERS``/``SHARDED_EPOCH_BUILDERS`` registries already key —

* ``DispatchProfiler`` / ``GLOBAL_PROFILER``: every builder in
  ops/fused_epoch.py, ops/fused_multi.py, ops/fused_sharded.py and the
  barrier-step jits in parallel/fused.py returns its jitted callable
  through ``profile_dispatch(jitted, qualname)``. The wrapper is pure
  host Python — it adds ZERO dispatches (the same reason
  count_dispatches' wrapper counts correctly) — and records per call:
  wall seconds (cumulative device-occupancy proxy on the synchronous
  CPU stand-in; enqueue latency on an async TPU backend), a
  jit-cache-miss/recompile event when the underlying executable cache
  grew during the call (compile seconds = that call's wall time), and
  a ``cat="dispatch"`` span into the PR-1 Chrome trace ring tagged
  with the current epoch — a slow epoch attributes to the dispatch
  that caused it.
* AOT cost/memory analysis: the first call through a wrapper snapshots
  the argument *avals* (ShapeDtypeStructs — no device buffers are
  retained), so ``analyze()`` can later ``.lower().compile()`` the
  already-traced callable and read XLA's static ``cost_analysis()``
  flops / bytes-accessed and ``memory_analysis()`` temp/arg/output
  bytes — chip-free on the CPU stand-in, for-real on TPU.
* ``hbm_ledger``: the cluster-wide memory ledger — per-job/per-executor
  state bytes (common/memory.py walks, federated from workers through
  the existing stats frame) summed with the analyzed peak temp bytes
  against ``[observability] hbm_capacity_bytes``, reporting headroom
  and flagging jobs approaching eviction-budget territory.
* ``roofline_report``: arithmetic intensity (flops / bytes accessed)
  of each analyzed kernel against configurable chip peak flops and
  HBM bandwidth — the artifact ROADMAP item 1's "measured roofline
  analysis" demands (``ctl profile roofline``).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional

from .tracing import CAT_DISPATCH, now_ns, span


class DispatchRecord:
    """Telemetry for one dispatch qualname (mutated lock-free on the
    hot path — single attribute bumps under the GIL).

    Two clocks per dispatch (profiler honesty under async dispatch):
    ``total_s``/``last_s``/``max_s`` time the ENQUEUE call — on an
    asynchronous backend (TPU always; the CPU stand-in's thread pool
    mostly) that is dispatch-submission latency and reads near-zero
    under pipelining. ``complete_s`` is the enqueue→host-visible wall
    time, resolved when a ``common/fetch.py`` future over the
    dispatch's outputs lands — an upper bound on device latency that
    includes any host think-time the pipeline deliberately overlapped.
    """

    __slots__ = ("name", "calls", "total_s", "last_s", "max_s",
                 "compiles", "compile_s", "complete_calls", "complete_s",
                 "complete_last_s", "inflight")

    #: enqueue timestamps awaiting a completion callback; bounded so
    #: dispatches whose outputs are never fetched cannot grow it
    INFLIGHT_CAP = 8

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.total_s = 0.0
        self.last_s = 0.0
        self.max_s = 0.0
        self.compiles = 0
        self.compile_s = 0.0
        self.complete_calls = 0
        self.complete_s = 0.0
        self.complete_last_s = 0.0
        self.inflight: list = []

    def to_dict(self) -> dict:
        d = {"calls": self.calls,
             "total_s": round(self.total_s, 6),
             "last_ms": round(self.last_s * 1e3, 4),
             "max_ms": round(self.max_s * 1e3, 4),
             "mean_ms": round(self.total_s / self.calls * 1e3, 4)
             if self.calls else 0.0,
             "compiles": self.compiles,
             "compile_s": round(self.compile_s, 4)}
        if self.complete_calls:
            d["complete_calls"] = self.complete_calls
            d["complete_s"] = round(self.complete_s, 6)
            d["complete_last_ms"] = round(self.complete_last_s * 1e3, 4)
            d["complete_mean_ms"] = round(
                self.complete_s / self.complete_calls * 1e3, 4)
        return d


def _aval(x: Any) -> Any:
    """Arg → ShapeDtypeStruct for AOT lowering (device buffers must not
    be retained by the profiler); non-array args (static ints, None)
    pass through for static_argnums."""
    if hasattr(x, "shape") and hasattr(x, "dtype") \
            and not isinstance(x, (bool, int, float)):
        import jax
        sharding = getattr(x, "sharding", None)
        try:
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=sharding)
        except Exception:  # noqa: BLE001 - e.g. committed=False shardings
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class DispatchProfiler:
    """Process-global dispatch telemetry registry.

    Enabled by default: the hot path per dispatch is one enabled check,
    one ``tracing.span``, an executable-cache-size probe and a handful of
    attribute bumps — microseconds against a dispatch that
    crosses into XLA. ``[observability] profiling = false`` turns the
    wrapper into a single-attribute-check passthrough."""

    def __init__(self):
        self.enabled = True
        #: dispatch spans shorter than this skip the trace ring
        #: ([observability] dispatch_span_min_ms)
        self.span_min_ms = 0.0
        #: current epoch tag for dispatch spans (set by Session.tick)
        self.epoch: Optional[int] = None
        self._records: dict[str, DispatchRecord] = {}
        #: qualname -> (lowerable, arg avals, kwarg avals) for AOT
        self._lowerable: dict[str, tuple] = {}
        self._analyses: dict[str, dict] = {}
        self._lock = threading.Lock()
        #: async-pipeline occupancy: completions observed via
        #: note_complete, and the max number of enqueued-but-unresolved
        #: dispatches of one qualname seen at a resolve (a depth-2
        #: pipeline reads 2 here while the synchronous path reads 1)
        self.completions = 0
        self.max_inflight = 0

    # -- hot path --------------------------------------------------------------

    def wrap(self, jitted: Callable, name: Optional[str] = None) -> Callable:
        """Instrument one jitted callable. The wrapper forwards the AOT
        surface (``.lower``/``.trace``) exactly like count_dispatches'
        wrapper, so the two compose in either order and
        tests/test_pallas_compile.py keeps lowering through it."""
        name = name or getattr(jitted, "__qualname__",
                               getattr(jitted, "__name__", repr(jitted)))
        # the executable cache lives on the innermost real jit object
        # (wrap may sit on top of a count_dispatches wrapper)
        inner = jitted
        while hasattr(inner, "__wrapped_jit__"):
            inner = inner.__wrapped_jit__
        cache_size = getattr(inner, "_cache_size", None)
        profiler = self

        def wrapper(*args, **kwargs):
            if not profiler.enabled:
                return jitted(*args, **kwargs)
            rec = profiler._records.get(name)
            if rec is None:
                rec = profiler._record(name)
            if name not in profiler._lowerable:
                profiler._remember_aval(name, jitted, args, kwargs)
            before = cache_size() if cache_size is not None else None
            # a short dispatch stays out of the ring (span_min_ms); its
            # annotation is in a profiler's trace either way
            with span(name, epoch=profiler.epoch, cat=CAT_DISPATCH,
                      tid="dispatch", min_ms=profiler.span_min_ms) as sp:
                out = jitted(*args, **kwargs)
            dt = sp.dur_ns / 1e9
            rec.calls += 1
            # enqueue timestamp for completion latency (resolved when a
            # fetch future over this dispatch's outputs lands)
            if len(rec.inflight) < DispatchRecord.INFLIGHT_CAP:
                rec.inflight.append(sp.start_ns)
            rec.total_s += dt
            rec.last_s = dt
            if dt > rec.max_s:
                rec.max_s = dt
            if before is not None and cache_size() > before:
                rec.compiles += 1
                rec.compile_s += dt
            elif before is None and rec.calls == 1:
                rec.compiles += 1       # no cache probe: first call compiles
                rec.compile_s += dt
            return out

        wrapper.__qualname__ = name
        wrapper.__name__ = name.rsplit(".", 1)[-1]
        wrapper.lower = getattr(jitted, "lower", None)
        wrapper.trace = getattr(jitted, "trace", None)
        wrapper.__wrapped_jit__ = jitted
        return wrapper

    def _record(self, name: str) -> DispatchRecord:
        with self._lock:
            rec = self._records.get(name)
            if rec is None:
                rec = self._records[name] = DispatchRecord(name)
            return rec

    def note_complete(self, name: str) -> None:
        """A fetch future over ``name``'s outputs just resolved: record
        enqueue→host-visible latency against the OLDEST outstanding
        enqueue (FIFO matches the per-qualname dispatch order) and the
        pipeline occupancy at resolve time (common/fetch.py calls this;
        attribute bumps only, safe under the GIL)."""
        if not self.enabled:
            return
        rec = self._records.get(name)
        if rec is None or not rec.inflight:
            return
        depth = len(rec.inflight)
        dt = (now_ns() - rec.inflight.pop(0)) / 1e9
        rec.complete_calls += 1
        rec.complete_s += dt
        rec.complete_last_s = dt
        self.completions += 1
        if depth > self.max_inflight:
            self.max_inflight = depth

    def pipeline_stats(self) -> dict:
        """Occupancy snapshot for the async epoch pipeline."""
        return {"completions": self.completions,
                "max_inflight": self.max_inflight}

    def _remember_aval(self, name, jitted, args, kwargs) -> None:
        """Snapshot abstract arg shapes for later AOT analysis. No
        device buffers are retained, and the callable itself is held
        only weakly — a dropped engine's compiled executables must not
        live forever in the profiler."""
        try:
            import weakref

            import jax
            ref = weakref.ref(jitted)
            a = jax.tree_util.tree_map(_aval, args)
            k = jax.tree_util.tree_map(_aval, kwargs)
        except Exception:  # noqa: BLE001 - telemetry must never fail a job
            return
        with self._lock:
            self._lowerable.setdefault(name, (ref, a, k))

    # -- AOT cost / memory analysis --------------------------------------------

    def analyze(self, name: Optional[str] = None,
                force: bool = False) -> dict:
        """AOT-``lower().compile()`` recorded callables and read XLA's
        static cost/memory analysis. Expensive (a fresh compile per
        qualname) — run on demand (``ctl profile roofline``,
        ``Session.profile_report()``), never on the barrier path.
        Results are cached per qualname."""
        names = [name] if name is not None else list(self._lowerable)
        out: dict = {}
        for n in names:
            if not force and n in self._analyses:
                out[n] = self._analyses[n]
                continue
            entry = self._lowerable.get(n)
            if entry is None:
                continue
            ref, args, kwargs = entry
            jitted = ref()
            if jitted is None:          # engine dropped since recording
                out[n] = {"error": "callable no longer alive"}
                continue
            try:
                out[n] = self._analyses[n] = aot_analysis(
                    jitted, *args, **kwargs)
            except Exception as e:  # noqa: BLE001 - analysis is best-effort
                out[n] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def analyses(self) -> dict:
        """Completed analyses only (no recompiles triggered)."""
        return dict(self._analyses)

    def peak_temp_bytes(self) -> int:
        """Largest analyzed per-dispatch temp allocation — the scratch
        HBM one in-flight epoch needs on top of resident state."""
        return max((a.get("memory", {}).get("temp_bytes", 0)
                    for a in self._analyses.values()
                    if isinstance(a, dict)), default=0)

    # -- snapshots -------------------------------------------------------------

    def counts(self) -> dict:
        """{qualname: calls} — the live twin of count_dispatches."""
        return {n: r.calls for n, r in self._records.items()}

    def snapshot(self) -> dict:
        """Full per-qualname telemetry + any completed analyses."""
        out = {}
        for n, r in sorted(self._records.items()):
            d = r.to_dict()
            a = self._analyses.get(n)
            if a is not None and "error" not in a:
                d["cost"] = a.get("cost")
                d["memory"] = a.get("memory")
            out[n] = d
        return out

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._lowerable.clear()
            self._analyses.clear()
            self.completions = 0
            self.max_inflight = 0


#: the process-global registry every profiled dispatch site records to
GLOBAL_PROFILER = DispatchProfiler()


def profile_dispatch(jitted: Callable,
                     name: Optional[str] = None) -> Callable:
    """Instrument a jitted epoch/barrier-step callable against the
    process-global profiler (the seam ops/ and parallel/ builders
    return through)."""
    return GLOBAL_PROFILER.wrap(jitted, name)


def aot_analysis(jitted: Callable, *args, **kwargs) -> dict:
    """``.lower().compile()`` an already-traced callable (args may be
    ShapeDtypeStructs) and extract XLA's static analyses:

    * ``cost`` — flops + bytes accessed (→ arithmetic intensity)
    * ``memory`` — argument/output/temp/generated-code bytes (the temp
      figure is the per-dispatch HBM scratch the ledger charges)
    """
    lower = getattr(jitted, "lower", None)
    if lower is None:
        raise TypeError(f"{jitted!r} has no .lower AOT surface")
    compiled = lower(*args, **kwargs).compile()
    ca = compiled.cost_analysis() or {}
    cost = {"flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0)}
    mem: dict = {}
    ma = compiled.memory_analysis()
    if ma is not None:
        mem = {
            "arg_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "out_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
            "code_bytes": int(
                getattr(ma, "generated_code_size_in_bytes", 0)),
        }
    return {"cost": cost, "memory": mem}


def per_job_attribution(total_seconds: float, weights: dict) -> dict:
    """Split one fused dispatch qualname's measured wall seconds over
    its member jobs (the tick compiler's padded supergroups and
    mega-epochs run MANY jobs inside one dispatch record, so per-job
    cost must be attributed, not measured).

    ``weights``: {job: weight} — the per-job work proxy carried in the
    extended [J, 3] packed-stats layout (cumulative flushed-group
    counts, packed slot 0). Jobs with zero observed weight across the
    board fall back to an equal split; the result is an ESTIMATE
    (proportional model), not a per-job measurement."""
    jobs = list(weights)
    if not jobs:
        return {}
    total_w = float(sum(weights.values()))
    if total_w <= 0:
        share = float(total_seconds) / len(jobs)
        return {j: round(share, 9) for j in jobs}
    return {j: round(float(total_seconds) * float(w) / total_w, 9)
            for j, w in weights.items()}


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


def hbm_ledger(jobs: dict, capacity_bytes: int,
               peak_temp_bytes: int = 0,
               warn_fraction: float = 0.8) -> dict:
    """Cluster-wide HBM ledger. ``jobs``: {job: {"bytes": total,
    "executors": {ident: bytes}, "worker": wid-or-None}} — the federated
    per-job/per-executor state-bytes snapshot (common/memory.py walks,
    session + every worker). Resident state plus the analyzed peak
    per-dispatch temp bytes is charged against ``capacity_bytes``;
    a job whose own state + the peak temp reaches ``warn_fraction`` of
    capacity is flagged (eviction-budget territory: time to set
    agg_hbm_budget/join_hbm_budget or shard the job)."""
    capacity = int(capacity_bytes)
    state_total = sum(int(j.get("bytes", 0)) for j in jobs.values())
    used = state_total + int(peak_temp_bytes)
    flagged = sorted(
        name for name, j in jobs.items()
        if capacity > 0 and
        int(j.get("bytes", 0)) + peak_temp_bytes >= warn_fraction * capacity)
    return {
        "capacity_bytes": capacity,
        "state_bytes": state_total,
        "peak_temp_bytes": int(peak_temp_bytes),
        "used_bytes": used,
        "headroom_bytes": capacity - used,
        "utilization": round(used / capacity, 6) if capacity else 0.0,
        "warn_fraction": warn_fraction,
        "jobs": {name: dict(j) for name, j in sorted(jobs.items())},
        "flagged": flagged,
    }


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

#: published per-chip peaks, keyed by ``jax.devices()[0].device_kind``:
#: (peak FLOP/s bf16, HBM bytes/s, HBM bytes). A device that is not in
#: the table is an error, never a default.
#: "TPU v5 lite" = one TPU v5e chip — 197 TFLOP/s bf16, 819 GB/s, 16 GB
#: (Google Cloud documentation, "TPU v5e").
CHIP_PEAKS: dict = {
    "TPU v5 lite": (197e12, 819e9, 16e9),
}


class UnknownChipError(LookupError):
    """The attached device has no entry in ``CHIP_PEAKS`` and no explicit
    peaks were given."""


def chip_peaks(peak_flops: Optional[float] = None,
               peak_bandwidth: Optional[float] = None,
               device_kind: Optional[str] = None) -> tuple:
    """(peak FLOP/s, peak bytes/s) for a roofline: explicit values win
    (``[observability] chip_peak_flops`` / ``chip_peak_bandwidth``, or
    ``ctl profile roofline --peak-flops/--peak-bandwidth``); what is not
    given comes from ``CHIP_PEAKS`` by the attached device's kind, and an
    unknown kind raises ``UnknownChipError``."""
    if peak_flops and peak_bandwidth:
        return float(peak_flops), float(peak_bandwidth)
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    known = CHIP_PEAKS.get(device_kind)
    if known is None:
        raise UnknownChipError(
            f"no roofline peaks for device kind {device_kind!r} (known: "
            f"{sorted(CHIP_PEAKS)}); give both peaks explicitly")
    return (float(peak_flops or known[0]),
            float(peak_bandwidth or known[1]))



def roofline_report(analyses: dict, peak_flops: float,
                    peak_bandwidth: float) -> dict:
    """Place each analyzed kernel on the roofline: arithmetic intensity
    = flops / bytes accessed; attainable flops = min(peak,
    intensity · bandwidth); ``bound`` says which wall the kernel sits
    under. ``analyses``: {qualname: aot_analysis() result}."""
    critical = peak_flops / peak_bandwidth if peak_bandwidth else 0.0
    kernels: dict = {}
    for name, a in sorted(analyses.items()):
        if not isinstance(a, dict) or "error" in a:
            kernels[name] = {"error": (a or {}).get("error", "unanalyzed")}
            continue
        flops = a["cost"]["flops"]
        nbytes = a["cost"]["bytes_accessed"]
        intensity = flops / nbytes if nbytes else 0.0
        attainable = min(peak_flops, intensity * peak_bandwidth) \
            if peak_bandwidth else peak_flops
        kernels[name] = {
            "flops": flops,
            "bytes_accessed": nbytes,
            "intensity": round(intensity, 4),
            "bound": ("compute" if critical and intensity >= critical
                      else "memory"),
            "attainable_flops": attainable,
            "pct_of_peak_flops": round(100.0 * attainable / peak_flops, 3)
            if peak_flops else 0.0,
            "memory": a.get("memory", {}),
        }
    return {
        "peak_flops": peak_flops,
        "peak_bandwidth_bytes_per_s": peak_bandwidth,
        "critical_intensity": round(critical, 4),
        "kernels": kernels,
    }


def render_roofline_table(report: dict) -> str:
    rows = [("kernel", "gflops", "mbytes", "flops/byte", "bound",
             "% of peak")]
    for name, k in report["kernels"].items():
        if "error" in k:
            rows.append((name, "-", "-", "-", "error", k["error"]))
            continue
        rows.append((name,
                     f"{k['flops'] / 1e9:.3f}",
                     f"{k['bytes_accessed'] / 1e6:.3f}",
                     f"{k['intensity']:.3f}",
                     k["bound"],
                     f"{k['pct_of_peak_flops']:.3f}"))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
             for r in rows]
    lines.append(
        f"(peak {report['peak_flops'] / 1e12:.1f} TFLOP/s, "
        f"{report['peak_bandwidth_bytes_per_s'] / 1e9:.0f} GB/s, "
        f"critical intensity {report['critical_intensity']:.1f} "
        "flops/byte)")
    return "\n".join(lines)
