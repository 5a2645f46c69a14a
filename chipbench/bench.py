"""Run one cell of the benchmark once.

Everything that belongs to one configuration, traffic mix, metric, entry or
cell is a file that the harness finds by its name:

  configs/<config>.json    sizes as run, with ``reference`` naming
  references/<name>.py     its plain reference
  traffic/<mix>.json       the mix's parameters, with ``entry`` naming
  entries/<entry>.py       the adapter that drives the program
  metrics/<metric>.py      ``read(run)`` -> the metric's value, or None
  limits/<cell>.json       the limit of each number ``check.py`` compares

A run: set-up (JAX, the compile cache, weights and head drawn on the device
from the seed, the entry built, one warm-up round at the cell's shapes), then
the closed loop for ``seconds`` (the round in flight at the end finishes and
counts), then — with the program's state freed — the comparison with the
reference. ``--trace 1`` profiles the window and reports the per-layer
metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = ROOT / ".chipbench_trace"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(kind: str, name: str, data: Path = HERE):
    path = Path(data) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, workload: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in a run with or without trace."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


class CompileClock:
    """Seconds and programs JAX spends compiling inside the block."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        import jax

        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs
            self.compiles += event == self.EVENTS[-1]


@dataclass
class Run:
    """What a metric's reader may read."""

    cfg: dict
    device_kind: str
    setup_s: float
    window_s: float
    rounds: list = field(default_factory=list)   # per round: see run_cell
    memory: dict = field(default_factory=dict)
    trace: object = None                          # trace.Reading or None

    @property
    def peak(self) -> dict:
        from chipbench import costs

        return costs.peaks(self.device_kind)


def _profile_options():
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


def run_cell(workload: str, seed: int, seconds: float, trace: bool, t0: float,
             impl: str = "pallas", controls=(), log=sys.stderr,
             data: Path = HERE, spec_path: Path = ROOT / "BENCHMARK.json") -> dict:
    """One run of ``workload``; returns the result line's object. ``data`` is
    the directory the named files are found in, ``spec_path`` the
    benchmark's declaration."""
    import jax

    from chipbench import check, trace as trace_mod, traffic, weights
    from repro.common.compile_cache import use_compile_cache

    data = Path(data)
    spec = load_json(spec_path)
    cell = find_cell(spec, workload)
    cfg = load_json(data / "configs" / f"{cell['config']}.json")
    mix_spec = load_json(data / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(data / "limits" / f"{workload}.json")
    metric_specs = cell_metrics(spec, workload, trace)
    readers = {m["name"]: load_module("metrics", m["name"], data) for m in metric_specs}
    entry_mod = load_module("entries", mix_spec["entry"], data)

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]

    W = weights.draw(cfg, seed)
    head_w = weights.draw_head(cfg, seed)
    edges = weights.head_edges(cfg)
    jax.block_until_ready((W, head_w))
    entry = entry_mod.Entry(cfg, mix_spec, W, head_w, edges, impl=impl)
    mix = traffic.Mix(mix_spec, cfg["vocab_size"], seed)
    base_key = weights.key_from_seed(seed)

    def serve(i):
        prompts, plens = mix.round(i)
        return prompts, plens, entry.serve(prompts, plens,
                                           jax.random.fold_in(base_key, i + 2),
                                           mix.greedy(i))

    serve(traffic.WARMUP_ROUND)
    setup_s = time.perf_counter() - t0
    print(f"setup: {setup_s:.3f} s", file=log, flush=True)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=_profile_options())
    rounds, served = [], []
    with CompileClock() as clock:
        start = time.perf_counter()
        i = 0
        while time.perf_counter() - start < seconds:
            t_send = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.round"):
                prompts, plens, (tokens, lengths, phi, quants) = serve(i)
            t_reply = time.perf_counter()
            rounds.append({"t_send": t_send - start, "t_reply": t_reply - start,
                           "plens": np.asarray(plens), "lengths": np.asarray(lengths),
                           "greedy": mix.greedy(i), "timing": entry.timings[-1],
                           "tokens": tokens, "phi": phi, "quantiles": quants,
                           "prompts": prompts})
            i += 1
        window_s = time.perf_counter() - start
    if trace:
        jax.profiler.stop_trace()
    print(f"window: {window_s:.3f} s, {len(rounds)} rounds, "
          f"{clock.compiles} compiles inside it", file=log, flush=True)

    memory = dict(dev.memory_stats() or {})
    V, max_new = cfg["vocab_size"], mix.max_new
    failed, attempted = 0, 0
    for r in rounds:
        for b in range(mix.B):
            attempted += 1
            ok = (1 <= r["lengths"][b] <= max_new
                  and bool(np.all((r["tokens"][b] >= 0) & (r["tokens"][b] < V)))
                  and bool(np.all(np.isfinite(r["quantiles"][b]))))
            failed += not ok
            if ok and r["greedy"]:
                served.append({"prompt": r["prompts"][b, : r["plens"][b]],
                               "tokens": r["tokens"][b], "length": int(r["lengths"][b]),
                               "phi": r["phi"][b], "quantiles": r["quantiles"][b]})

    reading = None
    if trace:
        texts = entry.compiled_texts()
        paths = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        reading = trace_mod.reduce(paths[-1], programs=texts, scopes=entry_mod.SCOPES)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    entry.close()
    del entry

    run = Run(cfg=cfg, device_kind=dev.device_kind,
              setup_s=setup_s, window_s=window_s, rounds=rounds, memory=memory,
              trace=reading)
    metrics = {}
    for m in metric_specs:
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    reference = load_module("references", cfg["reference"], data)
    readings = check.compare(reference, cfg, mix_spec, W, head_w, edges, served, seed,
                             controls)
    checks = {name: {"value": readings["program"][name], "limit": float(limits[name])}
              for name in check.NAMES}
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    devs = jax.devices()
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": int(memory.get("peak_bytes_in_use", 0))}
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reading is not None:
        device["busy_s"], device["window_s"] = reading.busy_s, reading.window_s
        result["breakdown"] = {"device_ops": reading.top_ops,
                               "idle_gaps": reading.idle_gaps}
    result["window_compiles"] = clock.compiles
    result["checked"] = {"requests": readings["program"]["n_requests"],
                         "tokens": readings["program"]["n_tokens"]}
    if controls:
        result["controls"] = {k: v for k, v in readings.items() if k != "program"}
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=log, flush=True)
    result["checks"] = checks
    return result
