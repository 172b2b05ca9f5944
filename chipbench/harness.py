"""What every cell shares: lookup by name, the device check, the compile
count, the traced window, the per-layer readers and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration file is the one ``configs`` names, its traffic mix is
``chipbench/traffic/<traffic>.json``, and that file's ``driver`` names a
module of ``chipbench/drivers`` with four functions:

* ``setup(ctx) -> state``: build the system under test from the seed and
  warm up every shape the window uses;
* ``window(ctx, state, seconds) -> dict``: drive the timed path for at
  least ``seconds``; returns ``attempted``, ``failed``, the cell's
  end-to-end metrics under ``metrics`` and what the readers need under
  ``records``;
* ``release(ctx, state)``: free the program's device state;
* ``check(ctx, state, result) -> [(name, value, limit), ...]``: compare
  what the window produced with the plain reference.  A run is correct
  when every value is at most its limit.

A per-layer metric ``<name>`` is read by ``chipbench/metrics/<name>.py``,
or, where there is no such file, by the reader of its first dotted part
(``window_compiles.served`` by ``window_compiles.py``): a quantity split
by the end-to-end metric its cells report can share one reader.
``read(run)`` returns a number or ``None`` when it finds nothing.

A served backbone's ``arch`` names its plain reference and its operation
and byte counts, ``chipbench/reference/archs/<arch>.py`` and
``chipbench/counts/archs/<arch>.py`` (``arch_module``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "BENCHMARK.json"
HERE = ROOT / "chipbench"
OUT = ROOT / ".chipbench_out"
EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def seed_words(seed: int, n: int) -> list[int]:
    """``n`` 31-bit words drawn from ``seed`` (any whole number): the one
    path from the command line's seed to every key and generator."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, dtype=np.uint32)
    return [int(w) & 0x7FFFFFFF for w in state]


@dataclasses.dataclass
class Ctx:
    """One run of one cell."""

    name: str
    cell: dict
    config: dict
    traffic: dict
    seed: int
    bench: dict
    devices: list = dataclasses.field(default_factory=list)


def load_cell(name: str, bench: dict | None = None) -> Ctx:
    bench = load_json(BENCH) if bench is None else bench
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no cell {name!r} in {BENCH.name}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return Ctx(name, cell, config, traffic, 0, bench)


def driver_of(ctx: Ctx):
    return importlib.import_module(f"chipbench.drivers.{ctx.traffic['driver']}")


def arch_module(package: str, arch: str):
    """``chipbench/<package>/archs/<arch>.py``: what ``package``
    (``reference`` or ``counts``) knows of one served architecture, named
    by the ``arch`` of a backbone's entry in the configuration file."""
    if not arch.isidentifier():
        raise ValueError(f"served architecture {arch!r} is not a module name")
    name = f"chipbench.{package}.archs.{arch}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"no {package} for served architecture {arch!r}: "
                         f"chipbench/{package}/archs/{arch}.py is missing") from None


def require_chips(chips: int):
    """The devices of the run; ``None`` when JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chipbench: no TPU (JAX found {devices[0].platform!r}); no result")
        return None
    if len(devices) < chips:
        log(f"chipbench: the cell needs {chips} chips, JAX found {len(devices)}")
        return None
    return devices


class CompileCounter:
    """Counts lowerings and backend compiles while armed: a jit cache miss
    inside the measured window shows here."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.counts = {e: 0 for e in self.EVENTS}
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.counts:
            self.counts[event] += 1

    def arm(self):
        self.counts = {e: 0 for e in self.EVENTS}
        self.armed = True

    def disarm(self) -> dict:
        self.armed = False
        return {e.rsplit("/", 1)[-1]: n for e, n in self.counts.items()}


def metric_entries(ctx: Ctx, kind: str) -> list[dict]:
    """The cell's metrics of ``kind`` (``end_to_end`` or ``per_layer``)."""
    bench = ctx.bench
    reported = {m["name"] for m in bench["end_to_end"]
                if ctx.name in m.get("workloads", [ctx.name])}
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in reported]
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if ctx.name in m["workloads"]:
                out.append(m)
        elif m["moves"] in reported:
            out.append(m)
    return out


def read_metric(name: str, run: dict):
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def peaks_of(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return table[kind]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, t0: float) -> int:
    """The command line's run: the chip check, then ``execute``, then the
    result line."""
    ctx = load_cell(name)
    ctx.seed = seed
    devices = require_chips(int(ctx.cell["chips"]))
    if devices is None:
        return EXIT_NO_CHIP
    ctx.devices = devices
    out = execute(ctx, seconds, trace, t0=t0)
    for n, c in out["compared"].items():
        log(f"compared {n}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


def execute(ctx: Ctx, seconds: float, trace: bool, *, t0: float) -> dict:
    """Set up, warm up, measure, check: one run of ``ctx``'s cell on
    ``ctx.devices``.  Returns the result line as a dict."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = ctx.devices
    cache = enable_compile_cache()
    # Cache every program, however quickly it compiled: a later run of the
    # cell then compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"chipbench: {ctx.name} seed={ctx.seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache}")
    counter = CompileCounter()
    driver = driver_of(ctx)

    counter.arm()
    state = driver.setup(ctx)
    setup_compiles = counter.disarm()
    setup_s = time.perf_counter() - t0
    log(f"chipbench: set-up {setup_s!r} s; lowerings/compiles in set-up {setup_compiles}")

    tracer = None
    if trace:
        from chipbench import trace as trace_mod

        # A traced window is kept short: the trace grows with every op run.
        seconds = min(seconds, float(ctx.traffic.get("trace_seconds", seconds)))

        tracer = trace_mod.Tracer(OUT / "trace" / ctx.name)
        tracer.start()
    counter.arm()
    result = driver.window(ctx, state, seconds)
    window_compiles = counter.disarm()
    traced = tracer.stop() if tracer is not None else None
    log(f"chipbench: window {result['window_s']!r} s; lowerings/compiles in the "
        f"window {window_compiles}")

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak(devices),
    }
    metrics = {}
    breakdown = None
    if trace:
        used = devices[: int(ctx.cell["chips"])]
        summary = traced.summary([d.id for d in used])
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = summary["breakdown"]
        run = {"cell": ctx.cell, "config": ctx.config, "traffic": ctx.traffic,
               "window_s": result["window_s"], "records": result["records"],
               "window_compiles": window_compiles, "trace": traced, "devices": used,
               "peaks": peaks_of(devices[0].device_kind)}
        for m in metric_entries(ctx, "per_layer"):
            value = read_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        e2e = dict(result["metrics"], setup_s=setup_s)
        for m in metric_entries(ctx, "end_to_end"):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    driver.release(ctx, state)
    gc.collect()
    t_check = time.perf_counter()
    compared = driver.check(ctx, state, result)
    log(f"chipbench: reference check took {time.perf_counter() - t_check!r} s")
    correct = (bool(compared) and all(v <= lim for _, v, lim in compared)
               and result["failed"] == 0)
    out = {
        "correct": correct,
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {n: {"value": v, "limit": lim} for n, v, lim in compared}
    return out

