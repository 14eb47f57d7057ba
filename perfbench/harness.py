"""The run of one cell: set-up, measured window, trace, memory, check.

Driven by data: the cell's entry in BENCHMARK.json names a configuration
and a traffic mix; their files name the kind (generator), the reference
and the arithmetic; the per-layer metrics of the manifest each have a
reader under metrics/. Nothing here knows a cell, a model or a metric by
name.
"""

import importlib.util
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 3.0          # length of the traced slice of the window
TRACE_OFFSET_S = 1.0         # the slice starts this long after the window
MARKER = "perfbench.mark"
TRACE_DIR = os.path.join(ROOT, ".perfbench_trace")   # emptied by every traced run


class NoChipError(RuntimeError):
    pass


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(folder, name):
    """perfbench/<folder>/<name>.py, by file (names may hold dots)."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{folder}/{name}.py is not in the benchmark")
    modname = "perfbench_" + folder + "_" + name.replace(".", "_") \
        .replace("-", "_")
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_manifest(path=None):
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest, workload):
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            cfg = next(c for c in manifest["configs"]
                       if c["name"] == cell["config"])
            return cell, cfg
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def cell_files(cell, cfg_entry, tiny=False):
    """(configuration, traffic) of a cell as its files hold them. `tiny`
    lays tests/tiny/'s files over them key by key: the sizes of the CPU
    rehearsal and of the tests, never a cell's."""
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if tiny:
        config.update(load_json("tests", "tiny", cfg_entry["name"] + ".json"))
        traffic.update(load_json("tests", "tiny",
                                 cell["traffic"] + ".json"))
    return config, traffic


def require_chips(n):
    """The devices of this run, or NoChipError: nothing falls back."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChipError(
            f"JAX found no accelerator (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChipError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


class Run:
    """What one run of one cell knows and gathers. Kinds, references and
    metric readers take it as their only argument."""

    def __init__(self, cell, config, traffic, seed, seconds, trace,
                 devices, rehearsal=False):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.rehearsal = rehearsal
        self.arith = load_module("arith", config["arith"])
        self.reference = load_module("references", config["reference"])
        self.spans = []          # the benchmark's own (name, start_s, dur_s)
        self.window = None       # what the kind's window() returned
        self.counters = {}       # name -> number, filled by the harness
        self.traced = None       # reduce_trace() of the traced slice

    def subseed(self, tag):
        """A 31-bit seed for one purpose, from --seed (any whole number)."""
        ss = np.random.SeedSequence([self.seed, *tag.encode()])
        return int(ss.generate_state(1)[0]) & 0x7FFFFFFF

    def rng(self, tag):
        return np.random.default_rng([self.seed, *tag.encode()])

    @property
    def peaks(self):
        from perfbench.peaks import peaks_for

        return peaks_for(self.devices[0].device_kind)

    def host_spans(self, w0=None, w1=None):
        """Program spans (runtime/telemetry) and the benchmark's own, as
        (name, start_s, dur_s) on perf_counter, optionally clipped."""
        from deeplearning4j_tpu.runtime import telemetry

        out = [(s["name"], s["ts"], s["dur"])
               for s in telemetry.get_registry().trace.spans()
               if s["ph"] == "X"]
        out.extend(self.spans)
        if w0 is not None:
            out = [s for s in out if s[1] + s[2] > w0 and s[1] < w1]
        return out

    def program_spans(self, name):
        """The program's spans called `name` that began inside the window,
        as runtime/telemetry keeps them (dicts with ts, dur, args)."""
        from deeplearning4j_tpu.runtime import telemetry

        w0, w1 = self.window["t0"], self.window["t1"]
        return sorted((s for s in telemetry.get_registry().trace.spans()
                       if s["name"] == name and s["ph"] == "X"
                       and w0 <= s["ts"] <= w1), key=lambda s: s["ts"])

    def device_idle_share(self):
        """Percent of the traced slice in which no operation ran on the
        device, or None where nothing was traced."""
        if not self.traced:
            return None
        return 100.0 * (1.0 - self.traced["busy_s"] / self.traced["window_s"])

    def entry_device_ms(self, entry):
        """Device milliseconds of each execution of a jitted entry in the
        traced slice, or [] where nothing was traced."""
        from perfbench.tracered import entry_durations

        if not self.traced:
            return []
        return [1e3 * d for d in entry_durations(
            self.traced["modules"], entry, self.traced["w0"],
            self.traced["w1"])]


class _Tracer(threading.Thread):
    """Traces a slice of the window from a thread of its own, so that the
    window's own call (a blocking fit(), client threads) is untouched."""

    def __init__(self, run, out_dir):
        super().__init__(daemon=True)
        self.run_, self.out_dir = run, out_dir
        self.t0 = self.t1 = self.mark = None
        self.error = None
        self.go = threading.Event()

    def run(self):
        import jax

        try:
            self.go.wait()
            length = min(TRACE_SECONDS, self.run_.seconds / 2)
            time.sleep(min(TRACE_OFFSET_S, self.run_.seconds / 4))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(MARKER):
                self.mark = time.perf_counter()
            self.t0 = time.perf_counter()
            time.sleep(length)
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
        except Exception as e:      # reported by the harness, not lost
            self.error = e


def _reduce(run, tracer):
    from perfbench import tracered

    loaded = tracered.load_xplane(tracered.find_xplane(tracer.out_dir),
                                  marker=MARKER)
    if loaded["marker_s"] is None:
        raise RuntimeError("the trace lacks the benchmark's marker event")
    off = loaded["marker_s"] - tracer.mark
    w0, w1 = tracer.t0 + off, tracer.t1 + off
    spans = [(n, s + off, d) for n, s, d in
             run.host_spans(tracer.t0, tracer.t1)]
    red = tracered.reduce_trace(loaded, w0, w1, spans)
    red["w0"], red["w1"] = w0, w1
    return red


def memory_peak_bytes(devices):
    """Peak bytes of the fullest chip, as JAX's `memory_stats()` gives
    them. The TPU runtime counts live buffers under `peak_bytes_in_use`
    and what it set aside for the programs' temporaries under
    `peak_bytes_reserved`, apart from each other (PERF.md, section 3):
    the chip held both."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0))
                     + int(st.get("peak_bytes_reserved", 0)))
    return max(peaks)


def per_layer_metrics(manifest, run):
    """Each per-layer metric this cell lists, read by its own reader. A
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and run.cell["name"] not in m["workloads"]:
            continue
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(manifest, workload, seed, seconds, trace, *, t_start=None,
             devices=None, rehearsal=False):
    """One run. `rehearsal` is the CPU rehearsal and the tests: the cell at
    tests/tiny/'s sizes, no device metric; `devices` skips the look for a
    chip."""
    import jax

    from deeplearning4j_tpu.runtime import compile_cache

    t_start = time.perf_counter() if t_start is None else t_start
    cell, cfg_entry = find_cell(manifest, workload)
    if devices is None:
        devices = require_chips(cell["chips"])
    cache_dir = compile_cache.configure()
    config, traffic = cell_files(cell, cfg_entry, tiny=rehearsal)
    run = Run(cell, config, traffic, seed, seconds, trace, devices,
              rehearsal=rehearsal)
    kind = load_module("kinds", traffic["kind"])
    log(f"cell {workload} seed {seed} seconds {seconds} trace {int(trace)} "
        f"on {len(devices)} x {devices[0].device_kind}; compile cache "
        f"{cache_dir}")

    with compile_cache.PersistentCacheWatch() as setup_watch:
        state = kind.setup(run)
    run.counters["setup_compile_s"] = setup_watch.compile_seconds
    setup_s = time.perf_counter() - t_start

    tracer = None
    if trace and not rehearsal:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = _Tracer(run, TRACE_DIR)
        tracer.start()
    with compile_cache.PersistentCacheWatch() as win_watch:
        run.window = kind.window(run, state,
                                 tracer.go.set if tracer else lambda: None)
    run.counters["compiles_in_window"] = win_watch.hits + win_watch.misses
    if tracer is not None:
        tracer.join()
        if tracer.error is not None:
            raise tracer.error
    peak = memory_peak_bytes(devices)
    if tracer is not None:
        run.traced = _reduce(run, tracer)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    if rehearsal:
        metrics = {}                # a CPU run names no device metric
    elif trace:
        metrics = per_layer_metrics(manifest, run)
    else:
        metrics = {}
        for m in manifest["end_to_end"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            v = setup_s if m["name"] == "setup_s" \
                else run.window["metrics"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    # the program's state is freed before the reference runs: the
    # process's peak was read above and the reference may then use the chip
    t_chk = time.perf_counter()
    checks = kind.check(run, state)
    del state
    log(f"reference and comparison took {time.perf_counter() - t_chk:.1f}s")
    result = finish(run, metrics, checks, peak)
    if rehearsal:
        result["rehearsal"] = True
    return result


def finish(run, metrics, checks, peak):
    """The result line's object; `checks` is [(name, value, limit)] and
    comes last, each number beside its limit."""
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices), "memory_peak_bytes": peak}
    result = {"attempted": int(run.window["attempted"]),
              "failed": int(run.window["failed"]),
              "metrics": metrics, "device": device}
    if run.traced:
        device["busy_s"] = run.traced["busy_s"]
        device["window_s"] = run.traced["window_s"]
        result["breakdown"] = {"device_ops": run.traced["device_ops"],
                               "idle_gaps": run.traced["idle_gaps"]}
    compared = {}
    correct = bool(checks) and run.window["failed"] == 0
    for name, value, limit in checks:
        ok = value is not None and np.isfinite(value) and value <= limit
        correct = correct and ok
        compared[name] = {"value": None if value is None else float(value),
                          "limit": float(limit), "ok": bool(ok)}
    result = {"correct": bool(correct), **result, "compared": compared}
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} limit {c['limit']} "
            f"{'ok' if c['ok'] else 'NOT OK'}")
    log(f"correct {result['correct']} attempted {result['attempted']} "
        f"failed {result['failed']}")
    return result


def log(msg):
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)
