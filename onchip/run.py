#!/usr/bin/env python3
"""The on-chip benchmark of stark-tpu.

    python3 onchip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It needs the TPU (`--dry-run` is the only way onto the CPU: toy
sizes, Pallas interpreted, counts and no rate; the driver never uses it).  The
cell is found by name: `BENCHMARK.json` -> `workloads/<cell>.json` (its check
and the limits for `correct`), `configs/<config>.json` (sizes, model, sampler,
and by name its rows generator, plain reference and counts),
`traffic/<traffic>.json` (the driver function and its parameters), and every
metric of the manifest that lists the cell -> `metrics/<metric>.json` ->
`readers/<reader>.py`.  Drivers, checks, rows generators, references, counts,
readers and controls are files found by name (`drivers/`, `checks/`, `rows/`,
`references/`, `counts/`, `readers/`, `controls/`); this file knows no model.
A later PR adds a cell, a configuration or a metric by adding files and
manifest entries; it edits none that is there.

The last line of standard output is one JSON object (correct, attempted,
failed, metrics, device[, breakdown], compared); everything else is on earlier
lines, on standard error, or under `onchip/out/<cell>/`.
"""

import time

T_PROCESS = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

def say(msg):
    print(f"[onchip] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


_loaded = {}


def load_py(folder, name):
    """The plug-in `<folder>/<name>.py`, loaded once."""
    if (folder, name) not in _loaded:
        path = os.path.join(HERE, folder, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"onchip_{folder}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[folder, name] = mod
    return _loaded[folder, name]


def verdict(values, limits):
    """(correct, [[name, value, limit], ...]); a value that is not finite
    fails and is printed as null (the line stays strict JSON); so does one
    over its limit."""
    rows = [[k, values[k] if abs(values[k]) < float("inf") else None,
             limits[k]] for k in limits]
    return all(v is not None and v <= lim for _, v, lim in rows), rows


def find_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        sys.exit(f"onchip: no cell {name!r}; known: {sorted(cells)}")
    return manifest, cells[name]


def metrics_of(manifest, cell, kind):
    """Names of the manifest's metrics of `kind` that this cell reports."""
    return [m["name"] for m in manifest[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def require_device(dry_run, chips):
    import jax

    if dry_run:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"platform={device['platform']} kind={device['kind']!r} "
        f"devices={device['count']} jax={jax.__version__}"
        + (" DRY RUN (toy sizes, no rate is a measurement)" if dry_run else ""))
    if not dry_run and (device["platform"] != "tpu" or len(devs) < chips):
        sys.exit(f"onchip: the cell needs {chips} TPU chip(s) and jax found "
                 f"{device}; --dry-run is the CPU path")
    return device


def compile_cache():
    """The program's own placement: `JAX_COMPILATION_CACHE_DIR` if set, else
    the fixed `<checkout>/.jax_cache`.  Every program is kept, however quick
    its compilation, so that a second run compiles nothing."""
    import jax

    from stark_tpu.platform import enable_compilation_cache

    path = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Backend compilations that missed the persistent cache, from jax's own
    monitoring events: requests that went to the cache less the hits."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = 0
        monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits


class Profiler:
    """`--trace 1`: `jax.profiler` over a slice of steady steps inside the
    window, opened at the first step record the driver hands over (the
    traffic file's `step_event`; the runner's `block`) and closed at the first
    one after `seconds`.  Every step record leaves a marker on the profiler's
    clock."""

    def __init__(self, out, seconds, step_event):
        self.dir = os.path.join(out, "profile")
        self.seconds = seconds
        self.step_event = step_event
        self.t0 = None
        self.done = False
        self.marks = []  # the block records, in the order of their markers

    def on_record(self, rec):
        import jax

        if rec.get("event") != self.step_event or self.done:
            return
        if self.t0 is None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"onchip.block.{len(self.marks)}"):
            self.marks.append(rec)
        if time.perf_counter() - self.t0 >= self.seconds:
            self.close()

    def close(self):
        import jax

        if self.t0 is not None and not self.done:
            jax.profiler.stop_trace()
        self.done = True


def host_spans(marks, markers):
    """What the host did around each step record, on the profiler's clock:
    from the record's own timing of its host work where it has one
    (`t_diag_s`: the runner's gate; before it the wait for the device) and
    the marker the harness wrote at the record."""
    spans = []
    at = dict(markers)
    for i, rec in enumerate(marks):
        m = at.get(f"onchip.block.{i}")
        if m is None:
            continue
        gate = 1e9 * float(rec.get("t_diag_s", 0.0))
        spans.append(("host:gate", m - gate, m))
        nxt = at.get(f"onchip.block.{i + 1}")
        if nxt is not None:
            n_gate = 1e9 * float(marks[i + 1].get("t_diag_s", 0.0))
            spans.append(("host:checkpoint+dispatch+collect", m, nxt - n_gate))
    return spans


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--control", default=None,
                    help="controls/<name>.json: has to come out not correct")
    args = ap.parse_args(argv)

    manifest, cell = find_cell(args.workload)
    workload = load_json("workloads", cell["name"] + ".json")
    limits = workload["checks"]
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    seconds = args.seconds if args.seconds is not None else float(
        manifest["run_seconds"])
    if args.control:
        # a lower-precision path of the program, switched on before it is
        # imported
        control = load_json("controls", args.control + ".json")
        os.environ.update(control["env"])
        say(f"CONTROL {args.control}: {control['env']}")

    device = require_device(args.dry_run, cell["chips"])
    cache_dir = compile_cache()
    say(f"compile cache: {cache_dir}")
    counter = CompileCounter()

    out = os.path.join(HERE, "out", cell["name"])
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    from lib import peaks as libpeaks, tracered

    sizes = config["dry_run"] if args.dry_run else config["sizes"]
    profiler = Profiler(out, float(traffic.get("trace_seconds", 4.0)),
                        traffic.get("step_event", "block")
                        ) if args.trace else None
    clock, window = {}, {}

    def window_opens():
        window["setup_s"] = time.perf_counter() - T_PROCESS
        window["compiles0"] = counter.snapshot()
        say(f"window opens after {window['setup_s']:.1f} s of set-up")

    def window_closes():
        if profiler is not None:
            profiler.close()
        window["compiles1"] = counter.snapshot()
        say("window closed")

    env = {
        "config": config, "traffic": traffic, "sizes": sizes, "out": out,
        "seed": args.seed, "seconds": seconds, "clock": clock,
        "load": load_py,
        "hooks": {
            "on_record": (profiler.on_record if profiler is not None
                          else (lambda rec: None)),
            "window_opens": window_opens, "window_closes": window_closes,
        },
    }
    measured = load_py("drivers", traffic["driver"]).run(env)

    import jax

    # the devices drain what the entry left in flight, then memory is read:
    # the peak on the fullest chip
    jax.block_until_ready([jax.device_put(0.0, d) + 1
                           for d in jax.local_devices()])
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.local_devices())
    gc.collect()

    ctx = dict(measured, device=device, chips=cell["chips"], config=config,
               load=load_py, clock=clock, setup_s=window["setup_s"],
               compile_requests=window["compiles1"][0] - window["compiles0"][0],
               compile_hits=window["compiles1"][1] - window["compiles0"][1],
               dry_run=args.dry_run)
    if not args.dry_run:
        ctx["peaks"] = libpeaks.peaks(device["kind"])
    breakdown = None
    if profiler is not None:
        # a run whose driver handed over no step record traced nothing
        events = (tracered.load_xplane(profiler.dir)
                  if profiler.t0 is not None else [])
        ctx["trace_events"] = events
        b = tracered.busy(events)
        if b is not None:
            device["busy_s"], device["window_s"] = b["busy_s"], b["window_s"]
        spans = host_spans(profiler.marks, tracered.markers(events))
        breakdown = {"device_ops": tracered.top_ops(events),
                     "idle_gaps": tracered.idle_gaps(events, spans)}
        tracered.write_summary(events, os.path.join(out, "trace_summary.json"))
        shutil.rmtree(profiler.dir, ignore_errors=True)

    # correct: the reference, once the window has closed and the program's
    # state is freed; rows come from the seed again
    t = time.perf_counter()
    values = load_py("checks", workload["check"]).compare(
        measured, {"config": config, "sizes": sizes, "seed": args.seed,
                   "load": load_py}, list(limits))
    correct, compared = verdict(values, limits)
    clock["reference_s"] = time.perf_counter() - t

    def read(name):
        spec = load_json("metrics", name + ".json")
        return load_py("readers", spec.get("reader", name)).read(
            ctx, spec.get("params", {}))

    metrics = {}
    kind = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in manifest[kind]}
    for name in metrics_of(manifest, cell, kind):
        value = read(name)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}

    line = {
        "correct": correct, "attempted": measured["attempted"],
        "failed": measured["failed"], "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
        # readings: numbers a traced run also makes that are no metric of the
        # manifest (the driver ignores the key); the cell's file lists them
        line["readings"] = {n: read(n) for n in workload.get("readings", [])}
    if "roofline" in ctx:
        line["roofline"] = ctx["roofline"]
    line["clock"] = {k: round(v, 3) for k, v in clock.items()}
    line["compared"] = compared
    say("clock: " + json.dumps(line["clock"]))
    for name, value, limit in compared:
        say(f"compared {name} = {value!r}  limit {limit!r}  "
            f"{'ok' if value is not None and value <= limit else 'OVER'}")
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
