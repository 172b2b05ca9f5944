"""The readers of the program's spans and scopes (``chipbench/program_spans.py``)
on trace files built by hand with known intervals, read back as a run's
trace is (``chipbench/trace.py``), then on the recorded chip trace
``chipbench/testdata/fleet1024-sweep.xplane.pb.gz`` where there is one."""
from __future__ import annotations

import math
import types
from pathlib import Path

import pytest

from chipbench import harness
from chipbench import program_spans as ps
from chipbench.trace import Events, Traced

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "fleet1024-sweep.xplane.pb.gz"
READERS = ("idle_in_prepare_share.sweep", "idle_in_launch_share.sweep",
           "idle_in_wait_share.sweep", "idle_in_collect_share.sweep",
           "idle_unnamed_share.sweep", "wait_us_per_step.sweep",
           "scan_us_per_step.policies.sweep", "scan_us_per_step.synthesis.sweep")
IDLE = READERS[:5]
T0 = 1000  # every time below is in ns after T0


def _line(name, events, meta):
    out = [f'lines {{ name: "{name}" timestamp_ns: {T0}']
    for ev in events:
        key = meta.setdefault(ev[0], (len(meta) + 1, ev[3] if len(ev) > 3 else ""))[0]
        out.append(f"events {{ metadata_id: {key} offset_ps: {int(ev[1] * 1000)} "
                   f"duration_ps: {int(ev[2] * 1000)} }}")
    return " ".join(out) + " }"


def _plane(name, lines):
    meta = {}
    body = " ".join(_line(n, evs, meta) for n, evs in lines)
    for op, (key, scope) in meta.items():
        stats = f'stats {{ metadata_id: 1 str_value: "{scope}" }}' if scope else ""
        body += (f' event_metadata {{ key: {key} value {{ id: {key} name: "{op}" '
                 f"{stats} }} }}")
    body += f' stat_metadata {{ key: 1 value {{ id: 1 name: "{ps.SCOPE_STAT}" }} }}'
    return f'planes {{ name: "{name}" {body} }}'


def _run(tmp_path, ops, host, modules=(), chips=1, steps=10):
    """A run whose trace file holds ``ops`` (name, start, duration[,
    scope]) and ``modules`` on each of ``chips`` chips, and the host spans
    ``host`` (name, start, end)."""
    from jax.profiler import ProfileData

    planes = [_plane(f"/device:TPU:{i}", [("XLA Ops", ops), ("XLA Modules", modules)])
              for i in range(chips)]
    planes.append(_plane("/host:CPU", [("python3", [(n, s, e - s) for n, s, e in host])]))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(" ".join(planes)))
    return {"trace": Traced(Events.from_file(str(path)), 0.0, str(path)),
            "devices": [types.SimpleNamespace(id=i) for i in range(chips)],
            "records": {"scan_steps": steps, "programs": ("jit__stream_grid",)}}


# Window [0, 100): busy [10, 30) and [60, 70); the host is in prepare
# [0, 20), launch [20, 40), wait [40, 65) and collect [65, 80); a
# make_spec span [5, 8) sits inside prepare; [80, 100) is under no span.
HOST = [("chipbench:window", 0, 100), ("chipbench:grid", 0, 80),
        ("repro:sweep.prepare", 0, 20), ("repro:workload.make_spec", 5, 8),
        ("repro:sweep.launch", 20, 40), ("repro:sweep.wait", 40, 65),
        ("repro:sweep.collect", 65, 80)]
OPS = [("a", 10, 20), ("b", 60, 10)]


def test_idle_is_cut_at_span_edges_and_sums_to_the_idle_share(tmp_path):
    run = _run(tmp_path, OPS, HOST)
    shares = {name: harness.read_metric(name, run) for name in IDLE}
    # Idle [0, 10) prepare; [30, 40) launch; the gap [30, 60) straddles
    # launch and wait and is split at 40: [40, 60) wait; [70, 80) collect,
    # split from [80, 100) unnamed.
    assert shares == {"idle_in_prepare_share.sweep": 10.0,
                      "idle_in_launch_share.sweep": 10.0,
                      "idle_in_wait_share.sweep": 20.0,
                      "idle_in_collect_share.sweep": 10.0,
                      "idle_unnamed_share.sweep": 20.0}
    total = harness.read_metric("device_idle_share.sweep", run)
    assert math.isclose(sum(shares.values()), total, abs_tol=1e-9)


def test_shares_average_over_chips_and_clip_to_the_window(tmp_path):
    shift = [(n, s + 100, e + 100) for n, s, e in HOST]
    host = shift + [("repro:sweep.prepare", 50, 90), ("repro:sweep.wait", 195, 220)]
    ops = [(n, s + 100, d) for n, s, d in OPS] + [("late", 190, 30), ("early", 70, 35)]
    run = _run(tmp_path, ops, host, chips=2)
    shares = ps.idle_shares(run)
    # [100, 105) is busy from "early"; [190, 200) is busy from "late".
    assert math.isclose(shares["prepare"], 5.0) and math.isclose(shares["unnamed"], 10.0)
    assert math.isclose(sum(shares.values()),
                        harness.read_metric("device_idle_share.sweep", run))


def test_wait_is_host_time_per_scan_step(tmp_path):
    run = _run(tmp_path, OPS, HOST, steps=5)
    # 25 ns of wait over 5 steps: 5 ns, 0.005 us.
    assert math.isclose(harness.read_metric("wait_us_per_step.sweep", run), 0.005)


def test_stage_time_counts_leaf_ops_of_the_grid_program_once(tmp_path):
    mods = [("jit__stream_grid(123)", 0, 100), ("jit_other(456)", 100, 50)]
    body = "jit(_stream_grid)/while/body"
    ops = [("while", 0, 90, "jit(_stream_grid)/while"),
           ("fusion.1", 0, 10, f"{body}/synthesis/poisson"),
           ("fusion.2", 10, 30, f"{body}/policies/adaptive/mul"),
           ("fusion.3", 40, 20, f"{body}/policies/water_filling/sort"),
           ("fusion.4", 60, 5, f"{body}/queue/min"),
           ("copy", 95, 3),
           ("outside", 110, 30, f"{body}/policies/adaptive/mul")]
    run = _run(tmp_path, ops, HOST, modules=mods, steps=10)
    # policies: 30 + 20 ns over 10 steps; synthesis 10 ns; the while that
    # holds them, and the op of another program, count nowhere.
    assert math.isclose(harness.read_metric("scan_us_per_step.policies.sweep", run), 0.005)
    assert math.isclose(harness.read_metric("scan_us_per_step.synthesis.sweep", run), 0.001)
    assert ps.stage_ns(run)["queue"] == 5.0


def test_scopes_are_read_from_the_op_metadata(tmp_path):
    run = _run(tmp_path, [("fusion.7", 0, 4, "jit(f)/policies/adaptive/add"), ("copy", 5, 1)],
               HOST)
    assert ps.op_scopes(run["trace"].path) == {
        "/device:TPU:0": {"fusion.7": "jit(f)/policies/adaptive/add"}}


def test_idle_gaps_are_named_by_the_innermost_program_span(tmp_path):
    # Busy [20, 60): the gap [0, 20) lies under repro:sweep.prepare (and
    # grid), [60, 100) under the benchmark's grid alone.
    host = [("chipbench:window", 0, 100), ("chipbench:grid", 0, 100),
            ("repro:sweep.prepare", 0, 20)]
    run = _run(tmp_path, [("a", 20, 40)], host)
    trace = run["trace"]
    lo, hi = trace.window_bounds()
    gaps = trace.idle_gaps("/device:TPU:0", lo, hi)
    assert gaps == [["grid", 40e-9], ["repro:sweep.prepare", 20e-9]]
    assert trace.summary([0])["breakdown"]["idle_gaps"] == gaps
    # Nested program spans: make_spec [5, 8) inside prepare [0, 20); the
    # piece under no span at all is named so.
    run = _run(tmp_path, OPS, HOST)
    gaps = dict(run["trace"].idle_gaps("/device:TPU:0", 0 + T0, 100 + T0))
    assert gaps == {"repro:sweep.prepare": 7e-9, "repro:workload.make_spec": 3e-9,
                    "repro:sweep.launch": 10e-9, "repro:sweep.wait": 20e-9,
                    "repro:sweep.collect": 10e-9, "outside any span": 20e-9}


def test_readers_find_nothing_in_a_program_without_spans_or_scopes(tmp_path):
    host = [h for h in HOST if not h[0].startswith(ps.PREFIX)]
    run = _run(tmp_path, OPS, host, modules=[("jit__stream_grid(1)", 0, 100)])
    assert all(harness.read_metric(name, run) is None for name in READERS)


def test_every_per_layer_metric_has_a_reader_and_split_names_share_one():
    bench = harness.load_json(harness.BENCH)
    metrics = harness.HERE / "metrics"
    for m in bench["per_layer"]:
        name = m["name"]
        assert ((metrics / f"{name}.py").exists()
                or (metrics / f"{name.split('.')[0]}.py").exists()), name
    run = {"window_compiles": {"jaxpr_to_mlir_module_duration": 3}}
    assert harness.read_metric("window_compiles.sweep", run) == 3
    assert harness.read_metric("window_compiles.served", run) == 3


@pytest.fixture(scope="module")
def recorded():
    if not TRACE.exists():
        pytest.skip(f"no recorded trace at {TRACE}")
    return {"trace": Traced(Events.from_file(str(TRACE)), 0.0, str(TRACE)),
            "devices": [types.SimpleNamespace(id=0)],
            "records": {"scan_steps": 1000, "programs": ("jit__stream_grid",)}}


def test_readers_on_the_recorded_chip_trace(recorded):
    values = {name: harness.read_metric(name, recorded) for name in READERS}
    assert all(isinstance(v, float) and v >= 0 for v in values.values()), values
    idle = harness.read_metric("device_idle_share.sweep", recorded)
    assert math.isclose(sum(values[n] for n in IDLE), idle, abs_tol=1e-6)
    scan = harness.read_metric("scan_us_per_step.sweep", recorded)
    stages = (values["scan_us_per_step.policies.sweep"]
              + values["scan_us_per_step.synthesis.sweep"])
    assert 0 < stages <= scan
