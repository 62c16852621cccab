#!/usr/bin/env python3
"""benchmarks/run.py: one cell of BENCHMARK.json, once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It refuses to start unless JAX reports a TPU with the chips the
cell asks for. Everything that belongs to one cell is a file found by name:

    configs/<config>.json          the deployment (names its driver)
    traffic/<traffic>.json         the mix's parameters
    drivers/<driver>.py            builds the deployment, warms up, measures, checks
    layer_metrics/<metric>.json    one per-layer metric: reader + arguments
    readers/<reader>.py            registry / spans / trace -> one number

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` turns on the
program's tracer and a profiler trace of a short steady sub-window and prints
the per-layer metrics. The last stdout line is the result object, whose last
key ``checks`` holds each number compared beside its limit (they are also the
last lines of standard error); everything else (sample counts, generator
lateness, the same comparisons as they are made) goes on earlier lines.

``--control <name>`` puts a deliberately broken reference in the program's
place (see the driver); such a run has to come out ``correct: false``. The
driver's checks never pass it.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys
import threading
import time

T_PROCESS_START = time.perf_counter()

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import trace_reduce  # noqa: E402  (benchmarks/trace_reduce.py)

NATIVE_LIBS = ("libscalarmath.so", "libkvlog.so", "libraftcore.so")
#: a traced sub-window: starts this share into the window, lasts this long
TRACE_AT = 0.35
TRACE_SECONDS = 4.0


class BenchError(Exception):
    """The run cannot produce a result (bad spec, no chip, broken set-up)."""


# -- the spec ------------------------------------------------------------------

def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module, found by name."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with the files it names."""

    def __init__(self, name: str, spec: dict | None = None):
        self.spec = spec if spec is not None \
            else load_json(ROOT / "BENCHMARK.json")
        rows = [w for w in self.spec["workloads"] if w["name"] == name]
        if not rows:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.row = rows[0]
        self.chips = int(self.row["chips"])
        cfg_row = next(c for c in self.spec["configs"]
                       if c["name"] == self.row["config"])
        self.config = load_json(ROOT / cfg_row["file"])
        self.traffic = load_json(
            BENCH / "traffic" / f"{self.row['traffic']}.json")
        self.driver_name = self.traffic.get("driver") or self.config["driver"]

    def reports(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end_names(self) -> list[str]:
        return [m["name"] for m in self.spec["end_to_end"] if self.reports(m)]

    def layer_metric_files(self) -> list[dict]:
        """Every ``layer_metrics/*.json`` whose cells include this one."""
        out = []
        for path in sorted((BENCH / "layer_metrics").glob("*.json")):
            lm = load_json(path)
            lm.setdefault("name", path.stem)
            if lm.get("workloads") is None or self.name in lm["workloads"]:
                out.append(lm)
        return out


# -- the device ----------------------------------------------------------------

def ensure_native() -> None:
    """Build native/ only if a loader would find no library (a checkout
    holds no .so: they are git-ignored)."""
    native = ROOT / "native"
    if all((native / lib).is_file() for lib in NATIVE_LIBS):
        return
    mk = subprocess.run(["make", "-C", str(native)], capture_output=True,
                        text=True, timeout=600)
    if mk.returncode != 0:
        raise BenchError(f"make -C native failed: {mk.stderr.strip()[-600:]}")


def find_device(chips: int) -> dict:
    """The device as JAX reports it; raises unless it is a TPU with at
    least ``chips`` chips. There is no CPU fallback."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU: {info}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return info


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # a backend without memory_stats (the CPU)
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- one run's context ---------------------------------------------------------

class RunContext:
    """What a driver gets: the cell, the seed, the window's length, whether
    this is the traced run, and the few services every driver needs."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 control: str | None = None, scale: dict | None = None,
                 quiet: bool = False):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = control
        #: tiny-size overrides of config/traffic keys, for the CPU rehearsal
        self.scale = dict(scale or {})
        self.quiet = quiet
        self.setup_s: float | None = None
        self.checks: list[dict] = []
        self.notes: list[dict] = []
        self.host_spans: list[dict] = []
        self._spans_lock = threading.Lock()
        self._trace_thread: threading.Thread | None = None
        #: (directory, wall clock at its start) of each traced segment
        self.trace_segments: list[tuple[pathlib.Path, float]] = []
        #: what tracing itself cost, per segment (seconds on the host clock)
        self.trace_costs: list[dict] = []
        self.state_dir = ROOT / ".bench_state" / f"run-{os.getpid()}"

    # parameters, with the rehearsal's overrides on top
    def param(self, key: str, default=None):
        if key in self.scale:
            return self.scale[key]
        if key in self.cell.traffic:
            return self.cell.traffic[key]
        return self.cell.config.get(key, default)

    def say(self, what: str, **fields) -> None:
        """An earlier line: never the result line."""
        row = {"note": what, **fields}
        self.notes.append(row)
        if not self.quiet:
            print(json.dumps(row, default=str), flush=True)

    def check(self, name: str, value, limit, ok: bool | None = None) -> bool:
        """One number compared, printed beside its limit. ``ok`` defaults
        to ``value <= limit``."""
        passed = bool(value <= limit) if ok is None else bool(ok)
        row = {"check": name, "value": value, "limit": limit, "ok": passed}
        self.checks.append(row)
        if not self.quiet:
            print(json.dumps(row, default=str), flush=True)
        return passed

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in self.checks)

    def window_opens(self) -> None:
        """Set-up ends here: process start -> first measured operation."""
        self.setup_s = time.perf_counter() - T_PROCESS_START
        if self.trace:
            self._trace_thread = threading.Thread(
                target=self._trace_window, daemon=True, name="bench-trace")
            self._trace_thread.start()

    def span(self, name: str):
        """A host span on the wall clock, recorded only in the traced run
        (idle gaps of the device are attributed to these)."""
        return _HostSpan(self, name) if self.trace else _NULL_SPAN

    def _trace_window(self) -> None:
        time.sleep(self.seconds * TRACE_AT)
        with self.traced():
            time.sleep(min(float(self.param("trace_seconds", TRACE_SECONDS)),
                           max(1.0, self.seconds / 3)))

    @contextlib.contextmanager
    def traced(self):
        """One traced segment (nothing unless this is the traced run). The
        first is the window's sub-window, which the trace readers read; a
        driver whose window holds no device call wraps the device call of
        its ``correct`` in a second one, so the result line's ``busy_s``
        and ``window_s`` are sums over the segments."""
        if not self.trace:
            yield
            return
        import jax
        trace_dir = self.state_dir / f"profile{len(self.trace_segments)}"
        trace_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        cost = {}
        self.trace_costs.append(cost)
        t0 = time.perf_counter()
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        cost["start_trace_s"] = time.perf_counter() - t0
        try:
            self.trace_segments.append((trace_dir, time.time()))
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
                yield
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            cost["stop_trace_s"] = time.perf_counter() - t0

    def trace_closes(self) -> None:
        if self._trace_thread is not None:
            self._trace_thread.join(timeout=300)

    def cleanup(self) -> None:
        shutil.rmtree(self.state_dir, ignore_errors=True)
        try:
            self.state_dir.parent.rmdir()
        except OSError:
            pass


class _HostSpan:
    __slots__ = ("ctx", "name", "t0")

    def __init__(self, ctx, name):
        self.ctx, self.name = ctx, name

    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *exc):
        row = {"name": self.name, "start_s": self.t0,
               "duration_s": time.time() - self.t0}
        with self.ctx._spans_lock:
            self.ctx.host_spans.append(row)
        return False


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


# -- per-layer metrics ---------------------------------------------------------

def read_layer_metrics(cell: Cell, data: dict) -> dict:
    """Run every per-layer metric's reader over what the traced run left
    behind. A reader that finds nothing returns None and is left out."""
    out = {}
    for lm in cell.layer_metric_files():
        reader = load_module("readers", lm["reader"])
        value = reader.read(data, **lm.get("args", {}))
        if value is not None:
            out[lm["name"]] = {"value": float(value), "unit": lm["unit"]}
    return out


def reduce_trace(ctx: RunContext, program_spans: list[dict],
                 gap_prefixes=("host.",)) -> list[dict]:
    """Each traced segment's profile -> busy/idle, kernel times, breakdown
    (trace_reduce.reduce), in the order the segments were traced."""
    spans = list(ctx.host_spans) + [
        {"name": s.get("name"), "start_s": s.get("start_s"),
         "duration_s": s.get("duration_s")} for s in program_spans]
    out = []
    for (trace_dir, wall_t0), cost in zip(ctx.trace_segments,
                                          ctx.trace_costs):
        files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        if files:
            t0 = time.perf_counter()
            events = trace_reduce.load_xplane(files[-1])
            t1 = time.perf_counter()
            out.append(trace_reduce.reduce(events, spans, wall_t0,
                                           gap_prefixes))
            cost.update(xplane_bytes=files[-1].stat().st_size,
                        load_s=t1 - t0, reduce_s=time.perf_counter() - t1)
    return out


def sum_breakdowns(segments: list[dict]) -> dict:
    """One breakdown over all traced segments, each list's top entries."""
    out = {}
    for key in ("device_ops", "idle_gaps"):
        total: dict[str, float] = {}
        for seg in segments:
            for name, seconds in seg["breakdown"][key]:
                total[name] = total.get(name, 0.0) + seconds
        out[key] = [list(kv) for kv in sorted(
            total.items(), key=lambda kv: kv[1],
            reverse=True)[:trace_reduce.TOP]]
    return out


# -- one cell, once ------------------------------------------------------------

def compared(checks: list[dict]) -> dict:
    """``ctx.checks`` as the result line carries them: name -> [value,
    limit, ok]; a name compared twice keeps both (``name#2``)."""
    out: dict = {}
    for c in checks:
        name, n = c["check"], 1
        while name in out:
            n += 1
            name = f"{c['check']}#{n}"
        out[name] = [c["value"], c["limit"], c["ok"]]
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: dict, control: str | None = None,
             scale: dict | None = None, quiet: bool = False,
             keep_trace: str | None = None,
             notes: list | None = None) -> dict:
    """Drive one run and return the result object (not printed here). The
    run's earlier lines are also appended to ``notes`` when given."""
    ctx = RunContext(cell, seed, seconds, trace, control=control, scale=scale,
                     quiet=quiet)
    if notes is not None:
        ctx.notes = notes
    driver = load_module("drivers", cell.driver_name)
    try:
        outcome = driver.run(ctx)
        ctx.trace_closes()
        result = {"correct": ctx.correct,
                  "attempted": int(outcome["attempted"]),
                  "failed": int(outcome["failed"])}
        dev = dict(device)
        dev["memory_peak_bytes"] = memory_peak_bytes()
        if not trace:
            e2e = dict(outcome["end_to_end"])
            e2e["setup_s"] = ctx.setup_s
            units = {m["name"]: m["unit"] for m in cell.spec["end_to_end"]}
            result["metrics"] = {
                name: {"value": float(e2e[name]), "unit": units[name]}
                for name in cell.end_to_end_names() if name in e2e}
        else:
            data = dict(outcome.get("layer_data", {}))
            data["cell"] = cell
            data["peaks"] = load_json(BENCH / "peaks.json")
            data["device"] = device
            segments = reduce_trace(ctx, data.get("spans", []),
                                    data.get("gap_prefixes", ("host.",)))
            # the trace readers read the window's own segment alone
            data["trace"] = segments[0] if segments else None
            result["metrics"] = read_layer_metrics(cell, data)
            if segments and keep_trace:
                trace_reduce.save_events(segments[0]["events"], keep_trace)
            if segments:
                dev["busy_s"] = sum(s["busy_s"] for s in segments)
                dev["window_s"] = sum(s["window_s"] for s in segments)
                result["breakdown"] = sum_breakdowns(segments)
                ctx.say("trace", segments=[
                    {"busy_s": s["busy_s"], "window_s": s["window_s"],
                     "busy_line": s["busy_line"],
                     "device_events": s["n_device_events"],
                     "clock_offset_known": s["clock_offset_known"]}
                    for s in segments], costs=ctx.trace_costs)
        result["device"] = dev
        # last in the line: each number compared, beside its limit
        result["checks"] = compared(ctx.checks)
        ctx.say("run", seconds_since_process_start=time.perf_counter()
                - T_PROCESS_START)
        return result
    finally:
        ctx.cleanup()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="run a deliberately broken stand-in (see the driver)")
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="with --trace 1: also write the flattened device "
                         "trace (the fixtures' form) to FILE")
    args = ap.parse_args(argv)
    try:
        cell = Cell(args.workload)
        ensure_native()
        device = find_device(cell.chips)
        from corda_tpu.utils.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        print(json.dumps({"note": "start", "workload": cell.name,
                          "seed": args.seed, "seconds": args.seconds,
                          "trace": args.trace, "control": args.control,
                          "compile_cache": str(cache_dir), **device}),
              flush=True)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          device, control=args.control,
                          keep_trace=args.keep_trace)
    except BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result, default=str), flush=True)
    # and as the last lines of standard error, where a run that is not
    # correct is read first
    for name, (value, limit, ok) in result["checks"].items():
        print(f"{'ok  ' if ok else 'FAIL'} {name} = {value} (limit {limit})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (batcher pools, raft pump) are stopped by
    # the driver; nothing may keep the interpreter from ending
    os._exit(rc)
