"""Run one cell of BENCHMARK.json on the accelerator this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run: set-up (the store, the corpus and the state made from
the seed, every program compiled or taken from the compile cache, the
traffic's set-up operations), then the window, then the correctness
check, then one JSON line on standard output.  ``--trace 0`` measures the
cell's end-to-end metrics over whole cycles of ``--seconds``; ``--trace 1``
traces the traffic's ``trace_cycles`` cycles and reports its per-layer
metrics.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import check, gen, reference  # noqa: E402
from bench.peaks import Peak, peak_for  # noqa: E402
from bench.progspans import program_span_names  # noqa: E402
from bench.trace import Spans, TraceSummary, capture, reduce  # noqa: E402


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = ROOT


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / config["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, w["chips"], cfg, traffic, mine(bench["end_to_end"]),
                mine(bench["per_layer"]), root)


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Run:
    """What a metric reader may read."""
    cell: Cell
    job: Any
    spans: Spans
    setup_s: float
    window_s: float
    host_peak_gib: float
    peak: Optional[Peak]
    trace: Optional[TraceSummary] = None


class CompileCounter:
    """Programs this process asked the backend for, from JAX's monitoring
    events: all of them (``count``, ``seconds``, cache reads included) and
    those the persistent cache did not hold (``misses``), which compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        self.count, self.seconds, self.misses = 0, 0.0, 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def event(self, event: str, **_) -> None:
        if event == self.MISS:
            self.misses += 1


_COMPILES: Optional[CompileCounter] = None


def compiles() -> CompileCounter:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_COMPILES)
        jax.monitoring.register_event_listener(_COMPILES.event)
    return _COMPILES


def enable_cache() -> None:
    """JAX's persistent compile cache where the program keeps it: the
    directory ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` in the
    checkout.  Every program goes in, however fast it compiled."""
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def host_peak_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def device_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def step_bytes(sys_) -> int:
    """Device bytes while one train step runs, as its compiled program states
    them: the state and batch it takes, what it returns less what it writes
    in place, and its temporaries."""
    m = sys_.step_fn.lower(sys_.state, sys_.batch_abs).compile().memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.temp_size_in_bytes)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             peak: Optional[Peak], t_start: float = T_START,
             dump_events: Optional[str] = None) -> dict:
    """Set-up, window, check; returns the result line as a dict."""
    from bench import loop, system

    enable_cache()
    counter = compiles()
    cfg, traffic = cell.cfg, cell.traffic
    spans = Spans()
    sys_ = system.build(cfg, seed, traffic["corpus_tokens"])
    job = loop.Job(sys_, cfg, traffic, seed, spans)
    job.run(traffic["setup"])
    setup_s = time.perf_counter() - t_start
    job.reset()
    spans.spans.clear()

    print(f"[phase] set-up {setup_s:.3f} s, {counter.count} programs ({counter.seconds:.3f} s), "
          f"{counter.misses} compiled; " + ", ".join(f"{op} {t:.3f}" for op, t in job.timeline),
          file=sys.stderr, flush=True)
    n_compiles, n_misses, n_ops = counter.count, counter.misses, len(job.timeline)
    summary = None
    if trace:
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
            window_s, events = capture(lambda: job.cycles(traffic["trace_cycles"]), d,
                                       loop.SPAN_NAMES + program_span_names())
        if dump_events:
            Path(dump_events).write_text(json.dumps(events))
        summary = reduce(events)
    else:
        window_s = job.window(seconds)
    print(f"[phase] window {window_s:.3f} s, {counter.count - n_compiles} programs, "
          f"{counter.misses - n_misses} compiled; "
          + ", ".join(f"{op} {t:.3f}" for op, t in job.timeline[n_ops:]),
          file=sys.stderr, flush=True)

    host_gib = host_peak_gib()
    used = jax.devices()[:cell.chips]
    # the allocator's peak need not count a program's temporaries: the step's
    # own footprint is a floor under the peak
    stats_peak, step_peak = device_peak_bytes(used), step_bytes(job.sys)
    mem_peak = max(stats_peak, step_peak)
    print(f"[memory] allocator peak {stats_peak} B, train step {step_peak} B",
          file=sys.stderr, flush=True)
    run = Run(cell, job, spans, setup_s, window_s, host_gib, peak, summary)
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in specs:
        value = metric_reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted = job.attempted()
    t_check = time.perf_counter()
    checks = correctness(cell, job, seed)
    print(f"[phase] check {time.perf_counter() - t_check:.3f} s", file=sys.stderr, flush=True)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": mem_peak}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": attempted, "failed": job.failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": [list(kv) for kv in summary.top_ops(10)],
                            "idle_gaps": [list(kv) for kv in summary.idle_gaps]}
    out["checks"] = checks
    return out


def correctness(cell: Cell, job, seed: int) -> Dict[str, Dict[str, float]]:
    """Every number compared, beside its limit (see ``bench.check``)."""
    cfg, limits = cell.cfg, cell.cfg["limits"]
    values: Dict[str, float] = {"save_readback_bytes_differ": sum(job.save_differ)}
    read = None
    if job.resume_gaps:   # one more resume, outside the window; its read is the read-back's
        read = job.read_back()
        manifest = job.put_on_device(*read)
        values["resume_bytes_differ"] = check.resumed_state(job, manifest)
    values["readback_bytes_differ"], values["digest_pages_differ"] = check.readback(
        job, seed, cfg["store"]["page_bytes"], read)
    if read is not None:
        del read
        job.first_step_after(manifest)
        values["resume_loss_gap"] = max(job.resume_gaps)
    prog = job.first
    job.sys.state = None   # the program's state is freed before the reference runs
    gc.collect()
    stream = gen.corpus(seed, cell.traffic["corpus_tokens"], cfg["vocab_size"])
    ref = reference.train_numbers(
        cfg, seed, gen.batches(stream, cfg["batch"], cfg["seq"], len(prog["losses"])))
    values.update(check.train_gaps(prog, ref))
    # a number with no limit in the configuration is read but not compared
    return {k: {"value": v if math.isfinite(v) else float("inf"), "limit": limits[k]}
            for k, v in values.items() if k in limits}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-events", default=None,
                    help="with --trace 1, also write the trace's kept events as JSON here")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.chips} chips asked for, {len(devices)} found", file=sys.stderr)
        return 2
    peak = peak_for(devices[0].device_kind)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), peak,
                   dump_events=args.dump_events)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
