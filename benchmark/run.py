#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Opens the cell's deployment (``configs/<config>.json``) on a fresh data
directory, warms up, drives barriers for ``--seconds`` under the cell's
traffic mix (``traffic/<mix>.json``), reads the MV back, frees the
program's state and compares the rows with the plain reference the
configuration names (``reference/<name>.py``). The last line of standard
output is the result; ``--trace 1`` reports the per-layer metrics
(``layer_metrics/<metric>.py``, one reader each) in place of the
end-to-end ones.

Everything that belongs to one cell, configuration, mix, reference or
per-layer metric is found by the name the data gives; this file names
none. A platform other than ``tpu``, fewer chips than the cell asks for or
a device that ``peaks.json`` does not know ends the run non-zero with no
result. ``--tiny`` rehearses the flow off the chip at the configuration's
``tiny`` sizes; it prints its result to standard error and still ends
non-zero: a rehearsal is never a result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import system, trace, window, work  # noqa: E402

ANNOTATION = "tick"
ANNOTATION_CHECKPOINT = "tick.checkpoint"


class BenchmarkError(Exception):
    """The run cannot give a result."""


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_by_name(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``, whatever characters a
    name may hold."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchmarkError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(spec: dict, name: str) -> tuple:
    """(cell, its configuration's entry) of BENCHMARK.json."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json "
                             f"(has: {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return cell, entry


def tiny_sizes(config: dict) -> dict:
    """The configuration at its rehearsal sizes."""
    out = dict(config)
    for key, value in config.get("tiny", {}).items():
        out[key] = ({**config[key], **value} if isinstance(value, dict)
                    else value)
    return out


def attached_device() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def find_device(chips: int, peaks_table: dict) -> tuple:
    """(device dict, its peaks); raises where this is not the machine the
    cell asks for."""
    dev = attached_device()
    if dev["platform"] != "tpu":
        raise BenchmarkError(f"platform is {dev['platform']!r}, not 'tpu': "
                             "no accelerator, no result")
    if dev["count"] < chips:
        raise BenchmarkError(f"{dev['count']} chips attached, the cell "
                             f"asks for {chips}")
    return dev, work.load_peaks(peaks_table, dev["kind"])


# -- compile accounting: JAX's own monitoring events --------------------------

def install_compile_listeners() -> dict:
    from jax import monitoring
    seen = {"backend_compiles": 0, "backend_compile_s": 0.0,
            "cache_hits": 0, "cache_misses": 0}

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["backend_compiles"] += 1
            seen["backend_compile_s"] += secs

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return seen


# -- the traced slice ---------------------------------------------------------

class Tracer:
    """Profiles ``count`` barriers of the window, each inside an
    annotation of the harness's own, from window barrier ``skip`` on, or
    from the first barrier at which the run's pace says that the seconds
    left cannot hold the barriers up to ``skip`` and the slice. The pace
    is the median time of the warm-up's last barriers (``warmup_s``) and of
    the window's barriers so far: a deployment slower than ``seconds /
    (skip + count)`` a barrier has its slice start earlier, every faster
    one at ``skip``."""

    def __init__(self, log_dir: str, skip: int, count: int,
                 warmup_s: tuple = (), keep_dir: str = ""):
        self.log_dir, self.skip, self.count = log_dir, skip, count
        self.warmup_s = list(warmup_s)
        self.keep_dir = keep_dir
        self.active = False
        self.traced: list = []

    def starts_at(self, i: int, seconds_left: float, barrier_s: list) -> bool:
        if self.active or self.traced or i > self.skip:
            return False
        if i == self.skip:
            return True
        pace = window.median(self.warmup_s + list(barrier_s))
        return pace is not None and \
            seconds_left < (self.skip - i + self.count) * pace

    def before(self, i: int, seconds_left: float, barrier_s: list) -> None:
        """The hook ``window.drive`` calls ahead of a barrier that runs."""
        if self.active and len(self.traced) >= self.count:
            self.stop()
        elif self.starts_at(i, seconds_left, barrier_s):
            self.start()

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no per-call Python events
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.active = True

    def around(self, i: int):
        import jax
        if not self.active:
            return contextlib.nullcontext()
        self.traced.append(i)
        return jax.profiler.TraceAnnotation(ANNOTATION)

    def stop(self) -> None:
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False

    def slice(self) -> dict:
        return {"first_traced": self.traced[0] if self.traced else None,
                "traced_barriers": len(self.traced)}

    def empty(self, why: str) -> None:
        """Says why a traced run has no device keys, and gives nothing."""
        print(f"benchmark/run.py: the trace is empty: {why}",
              file=sys.stderr, flush=True)
        say({"trace": {"empty": why, **self.slice()}})

    def read(self, checkpoint_of: list):
        """The reduced trace, its annotations renamed for the checkpoint
        barriers (the ledger says which those were); None, said, where no
        barrier was traced or no operation ran on the device."""
        if not self.traced:
            return self.empty("no barrier was traced")
        xplane = trace.find_xplane(self.log_dir)
        raw = trace.extract(xplane, (ANNOTATION,))
        for note, i in zip(raw["annotations"], self.traced):
            if checkpoint_of[i]:
                note[0] = ANNOTATION_CHECKPOINT
        if self.keep_dir:
            os.makedirs(self.keep_dir, exist_ok=True)
            with open(os.path.join(self.keep_dir, "outline.json"), "w") as f:
                json.dump(trace.outline(xplane), f)
            with gzip.open(os.path.join(self.keep_dir, "extract.json.gz"),
                           "wt") as f:
                json.dump(raw, f)
        reduced = trace.reduce(raw)
        if reduced is None:
            return self.empty(f"no device operation in {len(self.traced)} "
                              "traced barriers")
        return reduced


# -- one run ------------------------------------------------------------------

def generic_numbers(history: list, committed_epoch, frequency: int) -> dict:
    """What every deployment is held to besides its rows: no barrier
    failed, every checkpoint barrier committed, and the durable store
    holds the newest of them."""
    due = [h for h in history if h["epoch"] % frequency == 0]
    done = [h for h in due if h["checkpoint"] and h["result"] == "ok"
            and h["commit_ms"] is not None]
    newest = max((h["epoch"] for h in due), default=None)
    if newest is None:
        lag = 0
    elif committed_epoch is None:
        lag = newest
    else:
        lag = abs(newest - int(committed_epoch))
    return {"barriers_failed": sum(h["result"] != "ok" for h in history),
            "checkpoints_missing": len(due) - len(done),
            "committed_epoch_lag": lag}


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict,
             device: dict, peaks: dict, seed: int, seconds: float,
             traced: bool, control: str = "", keep_trace: str = "",
             t0: float = T0) -> dict:
    """Set up, measure, read back, free, compare. Returns the result
    line's object."""
    import jax

    compiles = install_compile_listeners()
    cache_dir = system.enable_compile_cache()
    per_barrier = sum(config["rows_per_chunk"].values()) \
        * config["chunks_per_tick"]
    warm = traffic["warmup_barriers"]
    max_barriers = config["max_events"] // per_barrier - warm
    if max_barriers < 1:
        raise BenchmarkError("max_events leaves no barrier to measure")

    work_dir = tempfile.mkdtemp(prefix="rw_benchmark_")
    try:
        sut = system.System(config, os.path.join(work_dir, "data"), seed)
        sut.create()
        warm_s = []                 # the last three set the traced run's pace
        for _ in range(warm):
            t_warm = time.perf_counter()
            sut.barrier()
            warm_s.append(time.perf_counter() - t_warm)
        tracer = Tracer(os.path.join(work_dir, "trace"),
                        traffic["trace_skip_barriers"],
                        traffic["trace_barriers"], warm_s[-3:], keep_trace) \
            if traced else None
        before_window = dict(compiles)
        setup_s = time.perf_counter() - t0
        win = window.drive(sut.barrier, seconds, max_barriers,
                           before=tracer.before if tracer else None,
                           around=tracer.around if tracer else None)
        if tracer:
            tracer.stop()
        in_window = {k: compiles[k] - before_window[k] for k in compiles}
        t_read = time.perf_counter()
        rows = sut.read_back()
        readback_s = time.perf_counter() - t_read
        history = sut.barrier_history()
        committed_epoch = sut.committed_epoch()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
        data_bytes = sum(os.path.getsize(os.path.join(base, f))
                         for base, _d, files in os.walk(sut.data_dir)
                         for f in files)
        sut.close()
        del sut
        gc.collect()

        n = len(win["barrier_s"])
        if len(history) < warm + n:
            raise BenchmarkError(f"the ledger holds {len(history)} barriers,"
                                 f" {warm + n} ran: raise observability."
                                 "barrier_history_capacity")
        ledger = history[-n:]
        barriers = [{"wall_ms": s * 1e3, "ledger": h}
                    for s, h in zip(win["barrier_s"], ledger)]
        reduced = tracer.read([h["checkpoint"] for h in ledger]) \
            if tracer else None

        # the plain reference, over everything warm-up and window ingested
        t_ref = time.perf_counter()
        ref = load_by_name("reference", config["reference"])
        expected = ref.expected(config, seed, warm + n)
        if control:
            rows = ref.expected(config, seed, warm + n, broken=control)["rows"]
        numbers = ref.compare(expected, rows)
        reference_s = time.perf_counter() - t_ref
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    numbers.update(generic_numbers(
        history, committed_epoch,
        config["rw_toml"]["streaming.checkpoint_frequency"]))
    floor = {"rows_expected": 1}          # an empty MV proves nothing
    compared = {}
    for name, value in numbers.items():
        if name in floor:
            compared[name] = {"value": value, "at_least": floor[name]}
        else:
            compared[name] = {"value": value, "limit": 0}
    correct = all(c["value"] >= c["at_least"] if "at_least" in c
                  else c["value"] <= c["limit"] for c in compared.values())

    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    if traced:
        ctx = {"barriers": barriers, "trace": reduced,
               "traced": tracer.traced, "first_barrier": warm,
               "config": config, "peaks": peaks,
               "events_per_barrier": per_barrier,
               "groups_touched": expected.get("groups_touched")}
        values = {}
        for metric in spec["per_layer"]:
            if cell["name"] not in metric.get("workloads", [cell["name"]]):
                continue
            value = load_by_name("layer_metrics", metric["name"]).read(ctx)
            if value is not None:
                values[metric["name"]] = value
    else:
        values = window.end_to_end(win["barrier_s"], win["elapsed_s"],
                                   per_barrier)
        values["setup_s"] = setup_s

    say({"window": {"barriers": n, "elapsed_s": win["elapsed_s"],
                    "stopped_by": win["stopped_by"],
                    "events": n * per_barrier,
                    "events_per_barrier": per_barrier,
                    "checkpoint_barriers": sum(h["checkpoint"]
                                               for h in ledger),
                    "barrier_median_ms": window.median(
                        win["barrier_s"]) * 1e3,
                    "barrier_max_ms": max(win["barrier_s"]) * 1e3,
                    "barrier_ms": [round(b * 1e3, 1)
                                   for b in win["barrier_s"]]},
         "setup_s": setup_s, "readback_s": readback_s,
         "reference_s": reference_s, "mv_rows": len(rows),
         "memory_peak_pct_of_hbm": (100.0 * peak / peaks["hbm_bytes"]
                                    if peaks else None),
         "data_dir_bytes": data_bytes, "compile_cache": cache_dir,
         "compiles_in_window": in_window, "compiles_total": dict(compiles),
         "control": control or None})
    if traced and reduced:
        say({"trace": {**tracer.slice(),
                       **{k: reduced[k] for k in
                          ("window_s", "busy_s", "devices", "program_s",
                           "program_runs")}}})
        if "work" in config:
            per = work.of(config, per_barrier,
                          expected["groups_touched"][warm])
            seconds_least, roof = work.least_seconds(per, peaks)
            say({"work": config["work"], "first_window_barrier": per,
                 "least_seconds": seconds_least, "binding_roof": roof})

    dev = {**device, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": n + 1,
              "failed": numbers["barriers_failed"],
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in values.items()},
              "device": dev}
    if traced and reduced:
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse off the chip at the configuration's "
                    "tiny sizes; never a result, ends non-zero")
    ap.add_argument("--control", action="store_true",
                    help="put the configuration's control (its reference "
                    "with one stated guarantee broken) in the program's "
                    "place at the read-back; the run has to come out not "
                    "correct")
    ap.add_argument("--keep-trace", default="",
                    help="with --trace 1: a directory to leave the trace's "
                    "outline and its extracted events in, to look at by hand")
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cell, entry = find_cell(spec, args.workload)
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    peaks_table = load_json(HERE, "peaks.json")
    control = config["control"] if args.control else ""
    if args.tiny:
        config = tiny_sizes(config)
        device = attached_device()
        peaks = peaks_table["device_kinds"].get(device["kind"])
    else:
        device, peaks = find_device(cell["chips"], peaks_table)
    result = run_cell(spec, cell, config, traffic, device, peaks, args.seed,
                      args.seconds, bool(args.trace), control,
                      args.keep_trace)
    for name, c in result["compared"].items():
        bound = (f"at least {c['at_least']}" if "at_least" in c
                 else f"limit {c['limit']}")
        print(f"compared {name}: {c['value']} ({bound})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    if args.tiny:
        print("rehearsal, not a result: " + json.dumps(result),
              file=sys.stderr, flush=True)
        return 1
    say(result)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BenchmarkError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr, flush=True)
        code = 2
    except Exception:  # noqa: BLE001 - no result line, a non-zero exit
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # hard exit: no lingering thread of the program may hold the process
    # (and the chip) past the result
    os._exit(code)
