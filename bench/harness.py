"""The benchmark harness: one run of one cell, driven by data.

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the cell (an entry of ``workloads``), its configuration (the
``file`` of its entry in ``configs``), its traffic mix
(``bench/traffic/<traffic>.json``) and each metric's reader
(``bench/metrics/<metric>.py``, a ``read(run)`` that returns a number,
or None when it finds nothing to read).  A new configuration, mix or
metric is new files plus new entries; no file here changes.

A run: check the chips, build the configuration's graph (from the
configuration's own seed: the data is the deployment's), start
``SparqlServer``, warm every (template, batch shape) the cell's
traffic can launch, serve the traffic for the window, read the metrics,
free the program's state, then compare a sample of the answers with the
plain reference (``bench/check.py``) and print the result line.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, devtrace, loops, traffic, watdiv

__all__ = ["Spec", "RunRecord", "run_cell", "percentile"]

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ".bench_cache"
#: constants per template that warm-up runs, the heaviest of each class
WARM_CONSTANTS = 3
#: spans of the program that label idle gaps in the device trace
LABEL_SPANS = ("queue", "parse", "plan", "execute", "device.launch",
               "demux", "decode")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between closest ranks."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


class Spec:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        with open(self.root / "BENCHMARK.json") as f:
            self.data = json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.data["configs"]:
            if c["name"] == cell["config"]:
                with open(self.root / c["file"]) as f:
                    return json.load(f)
        raise SystemExit(f"no configuration {cell['config']!r}")

    def mix(self, cell: dict) -> traffic.Mix:
        return traffic.load_mix(
            str(self.root / "bench" / "traffic" / f"{cell['traffic']}.json"))

    def metrics(self, cell: dict, traced: bool) -> List[dict]:
        """The cell's metrics: end-to-end ones untraced, per-layer traced."""
        group = self.data["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, name: str) -> Callable:
        path = self.root / "bench" / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


@dataclass
class RunRecord:
    """What the metric readers read."""

    cell: dict
    config: dict
    seconds: float
    setup_s: float = 0.0
    warmup_s: float = 0.0
    storage: Dict[str, float] = field(default_factory=dict)
    requests: List[loops.Sent] = field(default_factory=list)
    traces: list = field(default_factory=list)
    before: Dict[str, int] = field(default_factory=dict)
    after: Dict[str, int] = field(default_factory=dict)
    profile: Optional[dict] = None
    blocks: Dict[str, object] = field(default_factory=dict)

    def delta(self, key: str) -> int:
        return self.after[key] - self.before[key]


def check_devices(chips: int):
    """The accelerator devices, or exit non-zero before any work."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                         f"device(s); the benchmark measures only on a TPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout."""
    import jax

    path = str(Path(root) / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build(config: dict):
    """The configuration's graph, made from its own fixed seed (the
    deployment's data), loaded into the program.  Returns (triples,
    terms, class sizes, catalog)."""
    from repro.core.stats import build_catalog
    from repro.kernels import ops
    from repro.rdf.dictionary import Dictionary

    g = config["graph"]
    tt, terms, sch = watdiv.generate_watdiv(watdiv.WatDivConfig(
        scale_factor=g["scale_factor"], seed=g["seed"]))
    ops.use_pallas(bool(config.get("use_pallas", False)))
    catalog = build_catalog(tt, Dictionary.from_terms(terms),
                            threshold=config["threshold"],
                            with_extvp=config["with_extvp"],
                            build_backend=config["build_backend"])
    return tt, terms, watdiv.class_sizes(sch), catalog


def make_server(catalog, config: dict):
    from repro.runtime.config import RuntimeConfig
    from repro.serve import SparqlServer

    srv = config["server"]
    shapes = tuple(srv["batch_shapes"])
    runtime = RuntimeConfig(batch_shapes=shapes, max_batch=srv["max_batch"],
                            flush_ms=srv["flush_ms"], trace_sample_rate=0.0,
                            trace_ring=1 << 22, trace_slow_keep=0,
                            trace_cardinality=False)
    return SparqlServer(catalog, layout=config["layout"],
                        backend=config["backend"],
                        max_batch=srv["max_batch"], flush_ms=srv["flush_ms"],
                        batch_shapes=shapes, runtime=runtime)


def warmup(server, mix: traffic.Mix, sizes, tt, terms) -> None:
    """Every (template, batch shape) the window can launch, ready before
    it opens, the same in every run.  Each template runs at batch 1,
    through the server, with the ``WARM_CONSTANTS`` constants of its
    classes that match the most triples (``traffic.heaviest``): that
    prepares it, uploads its tables and grows its join capacities to
    what its heaviest constants need, so that the window's constants
    find them large enough and compile nothing.  Its program for every
    larger shape is then compiled, or read from the cache, at those
    capacities and with inputs of the window's types, without being
    run: a batch of B runs its B bindings one after another, so running
    it would cost B requests' device time that no request needs."""
    import jax.numpy as jnp

    shapes = [b for b in server.engine.batch_shapes if b > 1]
    for name in sorted(mix.templates):
        for q in traffic.heaviest(mix, name, sizes, tt, terms,
                                  WARM_CONSTANTS):
            server.query_batch([q])
        ex = getattr(server.engine.prepare(q), "executor", None)
        if ex is None or not shapes:
            continue
        rows, ns, tt_rows, tt_n, values = ex._device_inputs
        bounds = np.asarray(ex._default_bounds, dtype=np.int32)
        fconsts = np.asarray(ex.fconsts_from_mapping(None), dtype=np.int32)
        for b in shapes:
            ex._jitted_batch.lower(
                tuple(ex.caps), rows, ns, tt_rows, tt_n,
                jnp.asarray(np.stack([bounds] * b)),
                jnp.asarray(np.stack([fconsts] * b)), values).compile()


_COMPILES = [0]


def _count_compiles() -> None:
    """Count every program compiled or read from the persistent cache
    (JAX records one event per such request while the cache is on)."""
    import jax

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            _COMPILES[0] += 1

    if not getattr(_count_compiles, "on", False):
        jax.monitoring.register_event_listener(on_event)
        _count_compiles.on = True


def counters(server) -> Dict[str, int]:
    from repro.core import jexec

    m = server.metrics
    return {"batches": m.batches, "batched_requests": m.batched_requests,
            "padding": m.padding_slots, "fallbacks": m.device_fallbacks,
            "traces": jexec.trace_count(), "compiles": _COMPILES[0]}


def _program_spans(traces, offset_ns: float) -> List[devtrace.Event]:
    """The program's spans moved onto the profiler's clock."""
    out = []
    for ctx in traces:
        for s in ctx.spans:
            if s.name in LABEL_SPANS and s.t1 is not None:
                out.append(("program", "spans", s.name, s.t0 * 1e9 + offset_ns,
                            (s.t1 - s.t0) * 1e9))
    return out


def _reduce_profile(log_dir: str, t_window: float, traces) -> Optional[dict]:
    events = devtrace.load(log_dir)
    windows = [e for e in events if e[2] == devtrace.WINDOW]
    if not windows:
        return None
    w = windows[0]
    offset = w[3] - t_window * 1e9
    label = devtrace.host_labeller(events, _program_spans(traces, offset))
    return devtrace.reduce(events, (w[3], w[3] + w[4]), label)


def _annotate(traced: bool):
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def serve_window(server, mix, sizes, seed, seconds, sampler, traced,
                 record: RunRecord) -> float:
    """Serve the cell's traffic for ``seconds``; returns the window's
    start on the host clock."""
    import jax

    def on_done(r: loops.Sent) -> None:
        if r.error is None:
            sampler.offer(r.template, r.query, r.result)
        r.result = None          # only the sample is kept

    log_dir = None
    if traced:
        server.engine.config.trace_sample_rate = 1.0
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    note = _annotate(traced)
    if mix.loop == "open":
        schedule = traffic.open_schedule(mix, sizes, seed, seconds)
    else:
        source = traffic.requests(mix, sizes, seed)
    try:
        with note(devtrace.WINDOW):
            t0 = time.perf_counter()
            if mix.loop == "open":
                sent = loops.open_loop(server, schedule, t0, on_done,
                                       note=note, blocks=record.blocks)
            else:
                sent = loops.closed_loop(server, source, mix.clients,
                                         seconds, t0, on_done, note=note,
                                         blocks=record.blocks)
    finally:
        if traced:
            jax.profiler.stop_trace()
            server.engine.config.trace_sample_rate = 0.0
    record.requests = sent
    if traced:
        record.traces = [c for c in server.engine.tracer.recorder.traces()
                         if c.spans[0].t0 >= t0]
        try:
            record.profile = _reduce_profile(log_dir, t0, record.traces)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    return t0


def end_to_end(mix, record: RunRecord) -> Dict[str, float]:
    ok = [r for r in record.requests if r.error is None and r.done is not None]
    out = {"setup_s": record.setup_s}
    if mix.loop == "open":
        lat = [r.latency * 1e3 for r in ok]
        if lat:
            out["latency_p50_ms"] = percentile(lat, 50)
            out["latency_p95_ms"] = percentile(lat, 95)
    in_window = sum(1 for r in ok if r.done <= record.seconds)
    out["qps"] = in_window / record.seconds
    return out


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             root: Path = ROOT, require_tpu: bool = True,
             wrap_server: Optional[Callable] = None,
             t_start: Optional[float] = None) -> dict:
    """One run of one cell; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    if seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    spec = Spec(root)
    cell = spec.cell(workload)
    config = spec.config(cell)
    mix = spec.mix(cell)
    unknown = set(mix.templates) - set(config["query_templates"])
    if unknown:
        raise SystemExit(f"templates {sorted(unknown)} are not served by "
                         f"configuration {config['name']}")
    metrics = spec.metrics(cell, traced)
    import jax

    devs = check_devices(cell["chips"]) if require_tpu \
        else jax.devices()[:cell["chips"]]
    enable_cache(spec.root)
    _count_compiles()
    record = RunRecord(cell=cell, config=config, seconds=float(seconds))
    tt, terms, sizes, catalog = build(config)
    record.storage = catalog.storage_report()
    log(f"graph: {len(tt)} triples, {int(record.storage['extvp_tables'])} "
        f"ExtVP tables; VP {record.storage['vp_build_seconds']:.2f}s, "
        f"ExtVP {record.storage['extvp_build_seconds']:.2f}s")
    server = make_server(catalog, config)
    t_w = time.perf_counter()
    warmup(server, mix, sizes, tt, terms)
    record.warmup_s = time.perf_counter() - t_w
    if wrap_server is not None:
        wrap_server(server)
    record.before = counters(server)
    record.setup_s = time.perf_counter() - t_start
    log(f"set-up {record.setup_s:.2f}s (warm-up {record.warmup_s:.2f}s)")
    sampler = check.Sampler(seed)
    serve_window(server, mix, sizes, seed, seconds, sampler, traced, record)
    record.after = counters(server)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)

    values: Dict[str, float] = {}
    if traced:
        for m in metrics:
            v = spec.reader(m["name"])(record)
            if v is not None:
                values[m["name"]] = float(v)
    else:
        e2e = end_to_end(mix, record)
        values = {m["name"]: e2e[m["name"]] for m in metrics
                  if m["name"] in e2e}
    units = {m["name"]: m["unit"] for m in metrics}
    answered = sum(1 for r in record.requests
                   if r.error is None and r.done is not None)
    fallbacks = record.delta("fallbacks")
    failed = len(record.requests) - answered + fallbacks
    late = [(r.sent - r.due) * 1e3 for r in record.requests
            if mix.loop == "open"]

    # the program's state goes before the reference runs
    del server, catalog
    gc.collect()
    t_ref = time.perf_counter()
    from bench import reference

    graph = reference.Graph(tt, terms)
    verdict = check.compare(graph, sampler.sample(), failed)
    log(f"reference: {verdict['compared']} answers, "
        f"{verdict['reference_rows']} rows, "
        f"{time.perf_counter() - t_ref:.2f}s")
    for line in verdict["mismatches"]:
        log(f"mismatch: {line}")

    out = {"correct": verdict["correct"],
           "attempted": len(record.requests), "failed": failed,
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in values.items()},
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs),
                      "memory_peak_bytes": peak}}
    if traced and record.profile is not None:
        out["device"]["busy_s"] = record.profile["busy_s"]
        out["device"]["window_s"] = record.profile["window_s"]
        out["breakdown"] = record.profile["breakdown"]
    if late:
        out["generator_late_ms"] = {"p50": percentile(late, 50),
                                    "p95": percentile(late, 95),
                                    "max": max(late)}
    out["served"] = {"answered": answered, "fallbacks": fallbacks,
                     "batches": record.delta("batches"),
                     "padding_slots": record.delta("padding"),
                     "recompiles": record.delta("traces"),
                     "compiles": record.delta("compiles"),
                     "longest_call": record.blocks}
    out["checks"] = verdict["checks"]
    return out
