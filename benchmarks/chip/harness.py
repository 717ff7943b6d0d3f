"""One run of one benchmark cell: set-up, the timed window, the trace, the
metrics and the check that decides ``correct``.

A cell (a ``workloads`` entry of ``BENCHMARK.json``) names a configuration,
a traffic mix and a chip count.  Everything that belongs to one of them is
found by name: ``configs/<config>.json`` and ``configs/<config>.py`` (the
program's configuration, weights in the program's layout, the plain
reference forward, the model FLOPs), ``traffic/<traffic>.json``,
``metrics/<metric>.py`` (one reader per metric) and ``checks/<cell>.json``
(the limits of the numbers compared).

The run drives ``repro.core.coda.fit`` with the shard_map executor, built
once on a mesh of the cell's chips and handed to ``fit`` already built,
behind a probe that passes every call through:

* set-up: the weights are made from the seed on the device in one jitted
  call and put in the program's state when ``fit`` places it; the first
  ``SETUP_WINDOWS`` windows compile (or load) every program the window
  uses, and the probe reads the state after windows 1 and 3 for the check;
* window: from the next ``sample_window`` call until the first window
  boundary at or after ``--seconds`` (at most ``TRACE_SECONDS`` when
  traced); every window in it is whole, and no program may compile in it;
* after: the peak device memory is read, the program's state is freed and
  the reference follows the same three windows from the same weights and
  batches.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import math
import os
import pathlib
import shutil
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# Windows run before the timed window: the first compiles, the state after
# the first and after the third is read for the check.
SETUP_WINDOWS = 4
OBSERVED_WINDOWS = 3
SPANS = ("sample_window", "window_step")
# A traced run's window is at most this long: a trace holds every device
# operation (tens of thousands per ResNet-50 window), and reading it must
# stay well inside a run's time limit.
TRACE_SECONDS = 5.0


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def configure_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every program, so
    that only the first run of a cell in a checkout compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, traffic and
    limits.  ``cfg``/``traffic`` replace the files' contents (tests run a
    cell's path at a smaller size)."""

    def __init__(self, name: str, *, cfg: dict | None = None,
                 traffic: dict | None = None):
        self.manifest = load_json(ROOT / "BENCHMARK.json")
        by_name = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(by_name)}")
        w = by_name[name]
        self.name, self.chips = name, w["chips"]
        self.cfg = cfg or load_json(HERE / "configs" / f"{w['config']}.json")
        self.traffic = traffic or load_json(HERE / "traffic"
                                            / f"{w['traffic']}.json")
        self.model = load_module(HERE / "configs" / f"{w['config']}.py")
        limits = HERE / "checks" / f"{name}.json"
        self.limits = load_json(limits)["limits"] if limits.exists() else {}
        t = self.traffic
        self.K = t["workers_per_chip"] * self.chips
        self.I, self.B = t["interval"], t["per_worker_batch"]

    def metrics(self, trace: bool) -> list[dict]:
        """The manifest's metrics this cell reports: its end-to-end metrics
        untraced, its per-layer metrics traced.  A metric with a
        ``workloads`` list belongs to those cells; an end-to-end metric
        without one to every cell, a per-layer metric without one to every
        cell that reports the end-to-end metric it moves."""
        mine = lambda m: self.name in m.get("workloads", [self.name])
        e2e = [m for m in self.manifest["end_to_end"] if mine(m)]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]


class CompileClock:
    """Counts the backend compilations JAX reports while it is open."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)


class WindowClosed(Exception):
    """Raised from ``sample_window`` to end ``fit`` at the window's close."""


class Probe:
    """The executor ``fit`` is given: every call goes to the real one.
    ``place`` puts the benchmark's weights into the program's state; the
    window calls of set-up also read the state for the check."""

    def __init__(self, exe, weights, key):
        import jax

        from reference import change_norms

        self.exe, self.weights, self.key = exe, weights, key
        self.calls = 0
        self.losses = []
        self.readings = {}
        self.placed_at = None
        self.place_peaks = []
        self.last = None
        # the change is read against the program's proximal reference,
        # which holds the start weights until a stage ends (none does in a
        # run): no second copy of the weights on the device
        self._norms = jax.jit(change_norms)

    @property
    def overlap_pairs(self) -> bool:
        return getattr(self.exe, "overlap_pairs", False)

    def place(self, state):
        import jax

        K = self.exe.ccfg.n_workers
        stack = lambda t: jax.tree_util.tree_map(
            lambda l: jax.numpy.broadcast_to(l[None], (K,) + l.shape), t)
        mine = jax.eval_shape(lambda k: stack(self.weights(k)), self.key)
        theirs = jax.eval_shape(lambda: state["params"])
        if (jax.tree_util.tree_structure(mine)
                != jax.tree_util.tree_structure(theirs)
                or [l.shape for l in jax.tree_util.tree_leaves(mine)]
                != [l.shape for l in jax.tree_util.tree_leaves(theirs)]):
            raise SystemExit("the configuration's weights do not have the "
                             "program's parameter layout")
        sh = self.exe.state_shardings(state)
        # the allocator's peak once the program's own state is made, and
        # once the benchmark's weights have replaced it
        jax.block_until_ready(state)
        self.place_peaks.append(peak_bytes(self.exe.mesh.devices.flat))
        for leaf in jax.tree_util.tree_leaves((state["params"],
                                               state["ref_params"])):
            leaf.delete()
        make = jax.jit(lambda k: (stack(self.weights(k)),
                                  stack(self.weights(k))),
                       out_shardings=(sh["params"], sh["ref_params"]))
        params, ref = make(self.key)
        placed = self.exe.place(dict(state, params=params, ref_params=ref))
        jax.block_until_ready(placed)
        self.place_peaks.append(peak_bytes(self.exe.mesh.devices.flat))
        self.placed_at = time.perf_counter()
        return placed

    def window_step(self, state, wb, eta, **kw):
        import jax

        self.calls += 1
        if self.calls - 1 in (1, OBSERVED_WINDOWS):
            self.readings[self.calls - 1] = self._norms(
                state["params"], state["duals"], state["ref_params"])
        with jax.profiler.TraceAnnotation("window_step"):
            state, losses = self.exe.window_step(state, wb, eta, **kw)
        self.losses.append(losses)
        self.last = state
        return state, losses

    def stage_end(self, state, ab):
        return self.exe.stage_end(state, ab)


@dataclasses.dataclass
class RunInfo:
    """What a metric's reader sees of one run."""

    cell: Cell
    seconds: float             # the timed window, host clock
    windows: int
    samples: int
    setup_s: float
    peak_bytes: int
    input_s: list              # each timed window's sample_window span
    params_per_worker: int
    flops_per_sample: float
    peaks: dict
    trace: object = None       # trace_reduce.Trace of the window, or None
    window_s: list = dataclasses.field(default_factory=list)  # each window

    @property
    def steps(self) -> int:
        return self.windows * self.cell.I


def peak_bytes(devices) -> int:
    """The allocator's largest ``peak_bytes_in_use`` over ``devices``."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def device_check(chips: int) -> str:
    """An error message unless JAX finds at least ``chips`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX runs on {devs[0].platform}"
    if len(devs) < chips:
        return f"the cell asks for {chips} chips, JAX finds {len(devs)}"
    return ""


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def _program_readings(probe: Probe, K: int) -> dict:
    import numpy as np

    loss = np.stack([np.asarray(l, np.float64)
                     for l in probe.losses[:OBSERVED_WINDOWS]])
    out = {"loss": loss.reshape(OBSERVED_WINDOWS, -1, K)}
    for w, (ch, sp) in probe.readings.items():
        out[f"change{w}"] = np.asarray(ch, np.float64)
        out[f"spread{w}"] = np.asarray(sp, np.float64)
    return out


def train(cell: Cell, seed: int, seconds: float, *, trace: bool = False,
          t_process: float | None = None, score_range: bool = False) -> dict:
    """Set-up, then the timed window.  Returns what the metrics and the
    check need; the program's state is freed before it returns.
    ``score_range``: also the least and largest score of worker 0 on the
    first window's first batch under the final state."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    import generator
    from repro.core import coda, schedules
    from repro.launch import mesh as mesh_mod
    from repro.sharding import rules

    t_process = time.perf_counter() if t_process is None else t_process
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    cfg, traffic, K, I, B = cell.cfg, cell.traffic, cell.K, cell.I, cell.B
    cc = cfg["coda"]
    t_start = time.perf_counter()
    ds = generator.Dataset(seed, cell.model.inputs(cfg), traffic, K)
    t_data = time.perf_counter()
    mcfg = cell.model.model_config(cfg)
    ccfg = coda.CoDAConfig(n_workers=K, p_pos=traffic["p_pos"],
                           gamma=cc["gamma"],
                           impl=cc["impl"], use_window=cc["use_window"],
                           algorithm=cc["algorithm"], objective=cc["objective"],
                           optimizer=cc["optimizer"])
    mesh = mesh_mod.make_worker_mesh(n_devices=cell.chips)
    exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=mesh)
    weights = lambda k: cell.model.make_weights(k, cfg)
    wkey = generator.jax_key(seed, 4)
    probe = Probe(exe, weights, wkey)
    # window batches [I, K, B, ...] go straight to the executor's layout
    workers = rules.worker_partition(exe.mesh, exe.policy, K) or None
    sampler = generator.Sampler(
        ds, B, seed, NamedSharding(exe.mesh, PartitionSpec(None, workers)))

    host_batches: list = []
    win = {"n": 0, "t0": None, "t1": None, "spans": [], "trace_dir": None,
           "marks": [], "peaks": []}
    clock = CompileClock()

    def sample_window(key, n_steps):
        n = win["n"]
        win["n"] += 1
        win["marks"].append(time.perf_counter())
        if 0 < n <= SETUP_WINDOWS:
            # the allocator's peak after each set-up window (each ends in
            # fit's sync)
            win["peaks"].append(peak_bytes(mesh.devices.flat))
        if n < SETUP_WINDOWS:
            host = sampler.host_window(n_steps)
            if n < OBSERVED_WINDOWS:
                host_batches.append(host)
            return sampler.place(host)
        if n == SETUP_WINDOWS:
            if trace:
                win["trace_dir"] = tempfile.mkdtemp(prefix="chip_trace_")
                jax.profiler.start_trace(win["trace_dir"])
            win["compiles0"] = clock.count
            win["t0"] = time.perf_counter()
        elif time.perf_counter() - win["t0"] >= seconds:
            win["t1"] = time.perf_counter()
            win["compiles1"] = clock.count
            if trace:
                jax.profiler.stop_trace()
            raise WindowClosed
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("sample_window"):
            batch = sampler.place(sampler.host_window(n_steps))
        win["spans"].append(time.perf_counter() - t)
        return batch

    sched = schedules.ScheduleConfig(n_workers=K, eta0=cc["eta0"],
                                     T0=I * 10 ** 9, I0=I)
    with clock:
        try:
            coda.fit(generator.jax_key(seed, 5), mcfg, ccfg, sched, 1,
                     sample_window=sample_window,
                     sample_alpha_batch=sampler.alpha_batch,
                     executor=probe)
        except WindowClosed:
            pass
    windows = win["n"] - SETUP_WINDOWS - 1
    timed = [np.asarray(l) for l in probe.losses[SETUP_WINDOWS:]]
    failed = sum(int(not np.all(np.isfinite(l))) for l in timed)
    peak = peak_bytes(mesh.devices.flat)
    readings = _program_readings(probe, K)
    scores = None
    if score_range:
        params0 = jax.tree_util.tree_map(lambda l: l[0],
                                         probe.last["params"])
        first = jax.tree_util.tree_map(lambda l: l[0, 0], host_batches[0])
        h = np.asarray(jax.jit(lambda p, b: cell.model.score(p, b, cfg))(
            params0, first))
        scores = [float(h.min()), float(h.max())]
        del params0
    probe.last = None
    marks = win["marks"]
    setup_parts = {
        "imports": t_start - t_process, "data": t_data - t_start,
        "state": probe.placed_at - t_data,
        "window1": marks[1] - marks[0],
        "windows2-4": marks[SETUP_WINDOWS] - marks[1],
        "before_window1": marks[0] - probe.placed_at}
    params_per_worker = sum(
        math.prod(l.shape) for l in jax.tree_util.tree_leaves(
            jax.eval_shape(weights, wkey)))
    setup_peaks = probe.place_peaks + win["peaks"]
    del probe, exe
    gc.collect()
    return {
        "setup_s": win["t0"] - t_process, "seconds": win["t1"] - win["t0"],
        "windows": windows, "failed": failed, "peak": peak,
        "compiles": win["compiles1"] - win["compiles0"],
        "spans": win["spans"], "trace_dir": win["trace_dir"],
        "readings": readings, "batches": host_batches,
        "setup_parts": setup_parts, "scores": scores,
        "setup_peaks": setup_peaks,
        "window_s": [b - a for a, b in zip(marks[SETUP_WINDOWS:],
                                          marks[SETUP_WINDOWS + 1:])],
        "params_per_worker": params_per_worker,
        "device_kind": mesh.devices.flat[0].device_kind,
    }


def reference(cell: Cell, seed: int, batches: list, *,
              control: bool = False, precision: str | None = None,
              **fault) -> dict:
    """The reference's readings over the run's first windows, from the
    same weights (made anew from the seed) and batches, on one chip.
    ``control``: the same code in bfloat16.  ``precision``: another matmul
    precision than ``highest`` (float32) or ``default`` (the control)."""
    import jax
    import jax.numpy as jnp

    import generator
    import reference as ref

    cfg = cell.cfg
    cc = cfg["coda"]
    dtype = jnp.bfloat16 if control else jnp.float32
    precision = precision or ("default" if control else "highest")
    with jax.default_matmul_precision(precision):
        v0 = jax.jit(lambda k: cell.model.make_weights(k, cfg))(
            generator.jax_key(seed, 4))
        out = ref.run(cell.model.score, cfg, v0, batches, K=cell.K,
                      eta=cc["eta0"], gamma=cc["gamma"],
                      p=cell.traffic["p_pos"],
                      block_rows=cfg["reference_block_rows"], dtype=dtype,
                      **fault)
    del v0
    gc.collect()
    return out


def check_numbers(cell: Cell, got: dict, want: dict, compiles: int, *,
                  every: bool = False) -> dict:
    """Each number the cell compares, beside its limit (``every``: all the
    numbers ``reference.compare`` gives, for calibration)."""
    import reference as ref

    nums = ref.compare(got, want, K=cell.K)
    out = {k: {"value": v, "limit": cell.limits.get(k)}
           for k, v in nums.items() if every or k in cell.limits}
    out["window_compiles"] = {"value": compiles, "limit": 0}
    return out


def passed(checks: dict) -> bool:
    """Every number within its limit, and some number besides the compile
    count compared."""
    return len(checks) > 1 and all(
        c["limit"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())


def read_trace(trace_dir: str):
    import trace_reduce

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    try:
        return trace_reduce.load(paths[-1], SPANS) if paths else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float | None = None) -> tuple[dict, list[str]]:
    """One run: returns the result line's object and the check lines."""
    import jax

    import trace_reduce

    tr = train(cell, seed, seconds, trace=trace, t_process=t_process)
    want = reference(cell, seed, tr["batches"])
    checks = check_numbers(cell, tr["readings"], want, tr["compiles"])
    tdata = read_trace(tr["trace_dir"]) if trace else None
    info = RunInfo(
        cell=cell, seconds=tr["seconds"], windows=tr["windows"],
        samples=tr["windows"] * cell.I * cell.K * cell.B,
        setup_s=tr["setup_s"], peak_bytes=tr["peak"], input_s=tr["spans"],
        params_per_worker=tr["params_per_worker"],
        flops_per_sample=cell.model.flops_per_sample(cell.cfg, cell.traffic),
        peaks=peaks_for(tr["device_kind"]), trace=tdata,
        window_s=tr["window_s"])
    metrics = {}
    for m in cell.metrics(trace):
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(info)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": tr["peak"]}
    result = {"correct": passed(checks), "attempted": tr["windows"],
              "failed": tr["failed"], "metrics": metrics, "device": device}
    if trace and tdata is not None:
        chips = tdata.chips()
        device["busy_s"] = sum(trace_reduce.busy_ns(tdata, c)
                               for c in chips) / max(len(chips), 1) / 1e9
        device["window_s"] = tr["seconds"]
        result["breakdown"] = {
            "device_ops": [list(t) for t in trace_reduce.top_ops(tdata)],
            "idle_gaps": [list(t) for t in trace_reduce.idle_gaps(
                tdata, chips[0])] if chips else []}
    result["checks"] = checks
    parts = ", ".join(f"{k} {v:.2f}" for k, v in tr["setup_parts"].items())
    gib = lambda ps: ", ".join(f"{p / 2 ** 30:.4f}" for p in ps)
    made, placed, *windows = tr["setup_peaks"]
    ws = sorted(tr["window_s"])
    lines = [f"setup seconds: {parts}",
             f"peak GiB: the program's state made {gib([made])}, the "
             f"benchmark's weights placed {gib([placed])}, after set-up "
             f"windows 1-{len(windows)} {gib(windows)}, after the timed "
             f"window {gib([tr['peak']])}",
             f"timed windows: {len(ws)}, seconds median "
             f"{ws[len(ws) // 2] if ws else 0:.4f}, longest "
             + ", ".join(f"{w:.4f}" for w in ws[::-1][:3])]
    lines += [f"check {k}: {c['value']!r} limit {c['limit']!r}"
              for k, c in checks.items()]
    return result, lines
