"""Device profiling plane (common/profiling.py, ISSUE 12): per-dispatch
cost/memory telemetry keyed by the dispatch-counter qualnames, the
cluster-wide HBM ledger and AOT roofline analysis — plus the wiring
surfaces (Session.metrics()["profiling"] / ["dispatch"], Prometheus,
ctl profile)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from risingwave_tpu.common.dispatch_count import count_dispatches
from risingwave_tpu.common.profiling import (
    GLOBAL_PROFILER, aot_analysis, hbm_ledger, profile_dispatch,
    render_roofline_table, roofline_report,
)
from risingwave_tpu.common.tracing import CAT_DISPATCH, GLOBAL_TRACE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
Q5_EPOCH = "fused_source_agg_epoch.<locals>.epoch"
Q7_EPOCH = "fused_source_join_epoch.<locals>.epoch"


@pytest.fixture(autouse=True)
def _fresh_profiler():
    enabled, span_min = GLOBAL_PROFILER.enabled, GLOBAL_PROFILER.span_min_ms
    GLOBAL_PROFILER.reset()
    GLOBAL_PROFILER.enabled = True
    GLOBAL_PROFILER.span_min_ms = 0.0
    GLOBAL_PROFILER.epoch = None
    yield
    GLOBAL_PROFILER.reset()
    GLOBAL_PROFILER.enabled = enabled
    GLOBAL_PROFILER.span_min_ms = span_min


# ---------------------------------------------------------------------------
# DispatchProfiler core
# ---------------------------------------------------------------------------


def test_wrap_records_calls_seconds_and_compiles():
    f = profile_dispatch(jax.jit(lambda x: x * 2 + 1), "unit.f")
    x = jnp.arange(8.0)
    for _ in range(3):
        f(x)
    rec = GLOBAL_PROFILER.snapshot()["unit.f"]
    assert rec["calls"] == 3
    assert rec["total_s"] > 0 and rec["max_ms"] >= rec["last_ms"]
    # first call traced + compiled; the two cache hits did not
    assert rec["compiles"] == 1 and rec["compile_s"] > 0
    assert GLOBAL_PROFILER.counts() == {"unit.f": 3}


def test_recompile_detected_on_new_shape():
    f = profile_dispatch(jax.jit(lambda x: x + 1), "unit.reshape")
    f(jnp.arange(4.0))
    f(jnp.arange(4.0))
    assert GLOBAL_PROFILER.snapshot()["unit.reshape"]["compiles"] == 1
    f(jnp.arange(16.0))         # new shape -> jit cache miss
    assert GLOBAL_PROFILER.snapshot()["unit.reshape"]["compiles"] == 2


def test_disabled_profiler_is_passthrough():
    GLOBAL_PROFILER.enabled = False
    f = profile_dispatch(jax.jit(lambda x: x - 1), "unit.off")
    assert float(f(jnp.float32(3.0))) == 2.0
    assert "unit.off" not in GLOBAL_PROFILER.counts()


def test_dispatch_spans_land_in_trace_ring_with_epoch_tag():
    GLOBAL_TRACE.clear()
    GLOBAL_PROFILER.epoch = 7
    f = profile_dispatch(jax.jit(lambda x: x * x), "unit.span")
    f(jnp.arange(4.0))
    spans = [s for s in GLOBAL_TRACE.snapshot() if s.cat == CAT_DISPATCH]
    assert spans and spans[-1].name == "unit.span"
    assert spans[-1].epoch == 7 and spans[-1].tid == "dispatch"
    # span_min_ms filters sub-threshold dispatches out of the ring
    GLOBAL_TRACE.clear()
    GLOBAL_PROFILER.span_min_ms = 10_000.0
    f(jnp.arange(4.0))
    assert not [s for s in GLOBAL_TRACE.snapshot()
                if s.cat == CAT_DISPATCH]
    assert GLOBAL_PROFILER.counts()["unit.span"] == 2   # still counted


def test_aot_analysis_flops_bytes_memory():
    f = profile_dispatch(jax.jit(lambda a, b: a @ b), "unit.mm")
    a = jnp.ones((64, 64), jnp.float32)
    f(a, a)
    out = GLOBAL_PROFILER.analyze("unit.mm")["unit.mm"]
    # 64^3 mults + 64^2*63 adds; XLA reports 2*64^3-ish flops
    assert out["cost"]["flops"] >= 2 * 64 * 64 * 63
    assert out["cost"]["bytes_accessed"] >= 3 * 64 * 64 * 4
    assert out["memory"]["arg_bytes"] == 2 * 64 * 64 * 4
    assert out["memory"]["out_bytes"] == 64 * 64 * 4
    # cached: a second analyze() does not error and returns the same
    assert GLOBAL_PROFILER.analyze("unit.mm")["unit.mm"] is out
    # the snapshot carries the analysis once computed
    assert GLOBAL_PROFILER.snapshot()["unit.mm"]["cost"] == out["cost"]


def test_aot_analysis_direct_with_avals():
    jitted = jax.jit(lambda x: jnp.sum(x * 2.0))
    out = aot_analysis(jitted, jax.ShapeDtypeStruct((128,), jnp.float32))
    assert out["cost"]["flops"] > 0
    assert out["memory"]["arg_bytes"] == 128 * 4


# ---------------------------------------------------------------------------
# the acceptance invariant: profiling ON adds ZERO dispatches to the
# fused q5/q7 single-dispatch epochs
# ---------------------------------------------------------------------------


def _q5_fused(cap=128):
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.fused_epoch import fused_source_agg_epoch
    from risingwave_tpu.ops.grouped_agg import AggCore

    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=cap))
    exprs = [call("tumble_start", col(5, TIMESTAMP),
                  Literal(10_000_000, INT64)), col(0, INT64)]
    core = AggCore((INT64, INT64), (0, 1), [count_star()],
                   table_capacity=1 << 12, out_capacity=cap)
    return fused_source_agg_epoch(gen.chunk_fn(), exprs, core, cap), core


def _q7_fused(cap=128):
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.ops.fused_epoch import fused_source_join_epoch
    from risingwave_tpu.ops.interval_join import IntervalJoinCore

    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=cap))
    exprs = [call("tumble_start", col(5, TIMESTAMP),
                  Literal(10_000_000, INT64)),
             col(0, INT64), col(2, INT64)]
    schema = Schema((Field("window_start", TIMESTAMP),
                     Field("auction", INT64), Field("price", INT64)))
    core = IntervalJoinCore(schema, ts_col=0, val_col=2,
                            window_us=10_000_000, n_buckets=1 << 8,
                            lane_width=16)
    return fused_source_join_epoch(gen.chunk_fn(), exprs, core, cap), core


def test_profiling_adds_zero_dispatches_to_fused_q5():
    cap, k = 128, 4
    with count_dispatches() as c:
        fused, core = _q5_fused(cap)
        st = fused(core.init_state(), jnp.int64(0),
                   jax.random.PRNGKey(0), k)
        c.reset()
        for i in range(3):
            st = fused(st, jnp.int64((i + 1) * k * cap),
                       jax.random.PRNGKey(i + 1), k)
        # still EXACTLY one dispatch per epoch with profiling on
        assert c.counts[Q5_EPOCH] == 3, dict(c.counts)
    assert GLOBAL_PROFILER.counts()[Q5_EPOCH] == 4
    rec = GLOBAL_PROFILER.snapshot()[Q5_EPOCH]
    assert rec["compiles"] == 1 and rec["total_s"] > 0


def test_profiling_adds_zero_dispatches_to_fused_q7():
    cap, k = 128, 4
    with count_dispatches() as c:
        fused, core = _q7_fused(cap)
        out = fused(core.init_state(), jnp.int64(0),
                    jax.random.PRNGKey(0), k)
        c.reset()
        out = fused(out[0], jnp.int64(k * cap), jax.random.PRNGKey(1), k)
        assert c.counts[Q7_EPOCH] == 1, dict(c.counts)
    assert GLOBAL_PROFILER.counts()[Q7_EPOCH] == 2


def test_fused_epoch_aot_analysis_chip_free():
    """The roofline inputs exist on the CPU stand-in: AOT-lowering the
    recorded q5 epoch yields nonzero flops / bytes / temp figures
    without a chip (the ctl profile roofline path)."""
    cap, k = 128, 4
    fused, core = _q5_fused(cap)
    fused(core.init_state(), jnp.int64(0), jax.random.PRNGKey(0), k)
    a = GLOBAL_PROFILER.analyze(Q5_EPOCH)[Q5_EPOCH]
    assert a["cost"]["flops"] > 0 and a["cost"]["bytes_accessed"] > 0
    assert a["memory"]["temp_bytes"] > 0
    assert GLOBAL_PROFILER.peak_temp_bytes() == a["memory"]["temp_bytes"]


def test_profiled_epoch_still_lowers_for_tpu():
    """The wrapper must not eat the AOT surface the pallas-compile CI
    proxy drives (``.trace().lower(lowering_platforms=("tpu",))``)."""
    fused, core = _q5_fused(128)
    text = fused.trace(core.init_state(), jnp.int64(0),
                       jax.random.PRNGKey(0), 4).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "stablehlo" in text or "mhlo" in text


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


def test_hbm_ledger_headroom_and_flags():
    jobs = {
        "small": {"bytes": 100, "executors": {"HashAgg": 100},
                  "worker": None},
        "big": {"bytes": 900, "executors": {"HashJoin": 900}, "worker": 1},
    }
    led = hbm_ledger(jobs, capacity_bytes=2000, peak_temp_bytes=50,
                     warn_fraction=0.4)
    assert led["state_bytes"] == 1000
    assert led["used_bytes"] == 1050
    assert led["headroom_bytes"] == 950
    assert 0 < led["utilization"] < 1
    # big: 900 + 50 >= 0.4 * 2000 -> flagged; small: 150 < 800 -> not
    assert led["flagged"] == ["big"]
    assert led["jobs"]["big"]["worker"] == 1


def test_hbm_ledger_zero_capacity_never_divides():
    led = hbm_ledger({}, capacity_bytes=0)
    assert led["utilization"] == 0.0 and led["flagged"] == []


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------


def test_roofline_report_intensity_and_bounds():
    analyses = {
        "mem_bound": {"cost": {"flops": 1e6, "bytes_accessed": 1e6},
                      "memory": {"temp_bytes": 1}},
        "compute_bound": {"cost": {"flops": 1e9, "bytes_accessed": 1e3},
                          "memory": {}},
        "broken": {"error": "boom"},
    }
    rep = roofline_report(analyses, peak_flops=1e12, peak_bandwidth=1e10)
    assert rep["critical_intensity"] == 100.0
    mb = rep["kernels"]["mem_bound"]
    assert mb["intensity"] == 1.0 and mb["bound"] == "memory"
    assert mb["attainable_flops"] == 1e10
    assert mb["pct_of_peak_flops"] == 1.0
    cb = rep["kernels"]["compute_bound"]
    assert cb["bound"] == "compute" and cb["pct_of_peak_flops"] == 100.0
    assert "error" in rep["kernels"]["broken"]
    table = render_roofline_table(rep)
    assert "mem_bound" in table and "% of peak" in table


def test_chip_peaks_keyed_by_device_kind():
    """Peaks come from one table keyed by device_kind (v5e = "TPU v5
    lite": 197 TFLOP/s bf16, 819 GB/s); explicit values win; an unknown
    kind — the CPU these tests run on included — is an error, not a
    default."""
    from risingwave_tpu.common.profiling import UnknownChipError, chip_peaks

    assert chip_peaks(device_kind="TPU v5 lite") == (197e12, 819e9)
    assert chip_peaks(1e14, 1e12, device_kind="anything") == (1e14, 1e12)
    assert chip_peaks(1e14, None, device_kind="TPU v5 lite") \
        == (1e14, 819e9)
    with pytest.raises(UnknownChipError, match="cpu"):
        chip_peaks()                    # the attached device: a CPU here
    with pytest.raises(UnknownChipError):
        chip_peaks(1e14, None, device_kind="TPU v99")


@pytest.mark.slow
def test_ctl_profile_roofline_cli():
    """The acceptance artifact: `ctl profile roofline` emits per-kernel
    flops/bytes/intensity/%-of-peak for the q5 AND q7 fused epochs on
    the CPU stand-in, chip-free, via AOT lowering."""
    res = subprocess.run(
        [sys.executable, "-m", "risingwave_tpu", "ctl", "profile",
         "roofline", "--json", "--peak-flops", "1e14",
         "--peak-bandwidth", "1e12"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert res.returncode == 0, res.stderr
    rep = json.loads(res.stdout)
    assert rep["peak_flops"] == 1e14
    for qn in (Q5_EPOCH, Q7_EPOCH):
        k = rep["kernels"][qn]
        assert k["flops"] > 0 and k["bytes_accessed"] > 0
        assert k["bound"] in ("memory", "compute")
        assert 0 <= k["pct_of_peak_flops"] <= 100
        assert k["memory"]["temp_bytes"] > 0


# ---------------------------------------------------------------------------
# Session surfaces
# ---------------------------------------------------------------------------


def test_session_metrics_profiling_and_dispatch_sections():
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.prometheus import render_metrics

    cap, k = 128, 4
    fused, core = _q5_fused(cap)
    fused(core.init_state(), jnp.int64(0), jax.random.PRNGKey(0), k)
    s = Session()
    try:
        s.run_sql("CREATE TABLE t (a BIGINT, b BIGINT)")
        s.run_sql("CREATE MATERIALIZED VIEW m AS "
                  "SELECT a, count(*) AS c FROM t GROUP BY a")
        s.run_sql("INSERT INTO t VALUES (1, 10), (2, 20)")
        s.flush()
        m = s.metrics()
        prof = m["profiling"]
        assert prof["enabled"]
        rec = prof["dispatch"][Q5_EPOCH]
        assert rec["calls"] >= 1 and rec["total_s"] > 0 \
            and rec["compiles"] >= 1
        # HBM ledger over the live job's federated state bytes
        hbm = prof["hbm"]
        assert hbm["capacity_bytes"] == s.observability.hbm_capacity_bytes
        assert "m" in hbm["jobs"] and hbm["jobs"]["m"]["bytes"] > 0
        assert hbm["jobs"]["m"]["worker"] is None       # session-local
        assert hbm["headroom_bytes"] < hbm["capacity_bytes"]
        assert hbm["state_bytes"] >= hbm["jobs"]["m"]["bytes"]
        # the live dispatch-counter twin (satellite: reachable outside
        # bench --smoke / tests)
        assert m["dispatch"]["counts"][Q5_EPOCH] >= 1
        # Prometheus families
        text = render_metrics(s)
        assert "# TYPE rw_dispatch_total counter" in text
        assert "# TYPE rw_dispatch_seconds counter" in text
        assert "# TYPE rw_compile_total counter" in text
        assert 'rw_hbm_bytes{job="m",executor="_total"}' in text
        assert "rw_hbm_headroom_bytes " in text
    finally:
        s.close()


def test_session_dispatch_per_epoch_invariant_live():
    """metrics()["dispatch"]["per_epoch"] reads ~1.0 for a co-scheduled
    group's epoch qualname — the one-dispatch invariant, live."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.build import BuildConfig

    s = Session(config=BuildConfig(coschedule=True,
                                   agg_table_capacity=1 << 12),
                source_chunk_capacity=128)
    try:
        s.run_sql(
            "CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price "
            "BIGINT, channel VARCHAR, url VARCHAR, date_time TIMESTAMP, "
            "extra VARCHAR) WITH (connector = 'nexmark', "
            "nexmark_table = 'bid')")
        s.run_sql("CREATE MATERIALIZED VIEW m0 AS SELECT auction, "
                  "count(*) AS c FROM bid GROUP BY auction")
        GLOBAL_PROFILER.reset()          # drop the build-time compile call
        for _ in range(4):
            s.tick()
        d = s.metrics()["dispatch"]
        qn = "build_group_epoch.<locals>.coscheduled_epoch"
        assert d["counts"][qn] == 4
        assert d["per_epoch"][qn] == 1.0
        # the profiler's counts are cumulative across the process, so a
        # DROP + re-CREATE must retire the dead group's epochs or the
        # ratio would read 2.0 and falsely flag a dispatch regression
        s.run_sql("DROP MATERIALIZED VIEW m0")
        assert s._fused.retired[qn] == 4
        s.run_sql("CREATE MATERIALIZED VIEW m0 AS SELECT auction, "
                  "count(*) AS c FROM bid GROUP BY auction")
        for _ in range(4):
            s.tick()
        d = s.metrics()["dispatch"]
        assert d["counts"][qn] == 8
        assert d["per_epoch"][qn] == 1.0
    finally:
        s.close()


def test_session_dispatch_per_epoch_invariant_tick_compiled():
    """The tick compiler's twin of the invariant above (ISSUE 19
    satellite): the schedule DISSOLVES on every DDL, so a DROP +
    re-CREATE retires the dead padded group's epochs-run via
    TickCompiler.take_retired — otherwise the live per_epoch ratio
    would read 2.0 after the recompile and falsely flag a dispatch
    regression."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.build import BuildConfig
    from risingwave_tpu.stream.tick_compiler import PADDED_EPOCH_FN

    s = Session(config=BuildConfig(tick_compiler=True,
                                   agg_table_capacity=1 << 12),
                source_chunk_capacity=128)
    try:
        s.run_sql(
            "CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price "
            "BIGINT, channel VARCHAR, url VARCHAR, date_time TIMESTAMP, "
            "extra VARCHAR) WITH (connector = 'nexmark', "
            "nexmark_table = 'bid')")
        mv = ("CREATE MATERIALIZED VIEW {n} AS SELECT auction, "
              "sum(price + {lit}) AS v FROM bid GROUP BY auction")
        s.run_sql(mv.format(n="h0", lit=10))
        s.run_sql(mv.format(n="h1", lit=20))   # same skeleton => padded
        GLOBAL_PROFILER.reset()
        for _ in range(4):
            s.tick()
        d = s.metrics()["dispatch"]
        assert d["counts"][PADDED_EPOCH_FN] == 4
        assert d["per_epoch"][PADDED_EPOCH_FN] == 1.0
        # DROP dissolves the schedule: its 4 epochs-run must land in the
        # retirement ledger. Re-CREATE before the next tick so the
        # surviving singleton never runs a mega interlude.
        s.run_sql("DROP MATERIALIZED VIEW h1")
        assert s._fused.retired[PADDED_EPOCH_FN] == 4
        s.run_sql(mv.format(n="h1", lit=20))
        for _ in range(4):
            s.tick()
        d = s.metrics()["dispatch"]
        assert d["counts"][PADDED_EPOCH_FN] == 8
        assert d["per_epoch"][PADDED_EPOCH_FN] == 1.0
    finally:
        s.close()


@pytest.mark.slow
def test_hbm_ledger_federates_from_two_workers(tmp_path):
    """Acceptance: the ledger covers jobs hosted on >= 2 worker
    PROCESSES, attributed to their hosting worker, through the existing
    stats federation."""
    from risingwave_tpu.frontend import Session

    s = Session(workers=2, seed=11, data_dir=str(tmp_path / "c"))
    try:
        s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)")
        # grouped aggs: the HashAgg state is device arrays, so the
        # ledger charges real bytes for both worker-hosted jobs
        s.run_sql("CREATE MATERIALIZED VIEW m1 AS SELECT v, count(*) "
                  "AS c FROM t GROUP BY v")
        s.run_sql("CREATE MATERIALIZED VIEW m2 AS SELECT v, sum(k) "
                  "AS sk FROM t GROUP BY v")
        assert {"m1", "m2"} <= set(s._remote_specs)
        s.run_sql("INSERT INTO t VALUES (1, 10), (2, 20)")
        s.flush()
        hbm = s.metrics()["profiling"]["hbm"]
        owners = {name: j["worker"] for name, j in hbm["jobs"].items()
                  if name in ("m1", "m2")}
        assert len(owners) == 2
        assert sorted(set(owners.values())) == [0, 1], owners
        assert all(hbm["jobs"][n]["bytes"] > 0 for n in owners)
        assert hbm["state_bytes"] >= sum(
            hbm["jobs"][n]["bytes"] for n in owners)
    finally:
        s.close()


def test_observability_config_round_trip(tmp_path):
    """[observability] knobs load from TOML, round-trip through
    rw_config, and feed the session (span ring capacity + slow-epoch
    threshold moved here; [streaming] stays a legacy alias)."""
    from risingwave_tpu.common.config import load_config
    from risingwave_tpu.frontend import Session

    p = tmp_path / "rw.toml"
    p.write_text("""
[observability]
profiling = false
trace_ring_capacity = 512
slow_epoch_threshold_ms = 25.5
hbm_capacity_bytes = 1073741824
chip_peak_flops = 1e14
""")
    cfg = load_config(str(p))
    assert cfg.observability.profiling is False
    assert cfg.observability.trace_ring_capacity == 512
    assert cfg.observability.slow_epoch_threshold_ms == 25.5
    assert cfg.observability.hbm_capacity_bytes == 1 << 30
    assert cfg.observability.chip_peak_flops == 1e14
    ring0 = GLOBAL_TRACE.capacity
    s = Session(rw_config=cfg)
    try:
        assert s.observability.profiling is False
        assert GLOBAL_PROFILER.enabled is False
        assert s.slow_epoch_threshold_ms == 25.5
        assert GLOBAL_TRACE.capacity == 512
        assert s.metrics()["profiling"]["hbm"]["capacity_bytes"] == 1 << 30
    finally:
        s.close()
        GLOBAL_TRACE.set_capacity(ring0)

    # legacy [streaming] aliases still work when [observability] is
    # untouched
    p2 = tmp_path / "legacy.toml"
    p2.write_text("[streaming]\nslow_epoch_threshold_ms = 7.0\n")
    s2 = Session(rw_config=load_config(str(p2)))
    try:
        assert s2.slow_epoch_threshold_ms == 7.0
    finally:
        s2.close()

    # an [observability] value set to the documented DEFAULT still wins
    # over a legacy alias (unset-inherits is None, not value==default):
    # here the operator explicitly disables the detector while an old
    # [streaming] stanza still arms it
    p3 = tmp_path / "both.toml"
    p3.write_text("""
[streaming]
slow_epoch_threshold_ms = 7.0
[observability]
slow_epoch_threshold_ms = 0.0
""")
    s3 = Session(rw_config=load_config(str(p3)))
    try:
        assert s3.slow_epoch_threshold_ms == 0.0
    finally:
        s3.close()
