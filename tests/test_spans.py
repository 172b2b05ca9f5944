"""The sweep's host spans and the streaming kernel's named scopes.

Every entry point runs as four back-to-back ``repro:sweep.*`` spans on the
profiler's host plane (``core/spans.py``), sharing one ``call`` number and
carrying the grid's counts; ``workload.make_spec`` has a span of its own.
The streaming kernel's stages carry ``jax.named_scope`` names, so a device
trace can attribute each op.  These tests record a profiler trace of tiny
grids on the CPU and read the lowered kernel's debug text.
"""
from __future__ import annotations

import glob
import importlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import allocator as alloc
from repro.core import failures as fail_mod
from repro.core import spans, workload
from repro.core.agents import paper_fleet, synthetic_fleet
from repro.core.capacity import capacity_config
from repro.core.simulator import SimConfig

sweep_mod = importlib.import_module("repro.core.sweep")

HERE = Path(__file__).resolve().parent
SRC = HERE.parent / "src"

PHASES = ("prepare", "launch", "wait", "collect")
RATES = np.asarray([80.0, 40.0, 45.0, 25.0], np.float32)
STEPS = 40


def _host_spans(directory) -> list[tuple[str, float, float, dict]]:
    """(name, start_ns, end_ns, args) of every ``repro:`` span, by start."""
    files = glob.glob(str(directory / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert len(files) == 1, files
    data = ProfileData.from_file(files[0])
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    start = float(e.start_ns)
                    out.append((e.name[len(spans.PREFIX):], start,
                                start + float(e.duration_ns), dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(tmp_path)


def _call_spans(found, entry):
    return [s for s in found if s[0].startswith("sweep.") and s[3].get("entry") == entry]


def _specs():
    return workload.scenario_specs(RATES, STEPS, 3)


ENTRIES = {
    "sweep": lambda: sweep_mod.sweep(paper_fleet(), _specs(), shard=False),
    "sweep_fleets": lambda: sweep_mod.sweep_fleets(
        [synthetic_fleet(4, seed=0), synthetic_fleet(6, seed=1)],
        num_steps=STEPS, synthesize=True, shard=False),
    "sweep_workflows": lambda: sweep_mod.sweep_workflows(
        paper_fleet(), num_steps=STEPS, synthesize=True, shard=False),
    "sweep_capacity": lambda: sweep_mod.sweep_capacity(
        paper_fleet(), num_steps=STEPS, synthesize=True, shard=False),
}
# (batch, scenarios, agents) of each entry's grid above.
COUNTS = {"sweep": (1, 8, 4), "sweep_fleets": (2, 8, 6),
          "sweep_workflows": (5, 8, 4), "sweep_capacity": (4, 8, 4)}


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_entry_point_runs_as_four_phase_spans(tmp_path, entry):
    ENTRIES[entry]()  # compile outside the trace
    found = _traced(tmp_path, lambda: (ENTRIES[entry](), ENTRIES[entry]()))
    calls = _call_spans(found, entry)
    assert [n for n, *_ in calls] == [f"sweep.{p}" for p in PHASES] * 2
    for k in range(2):
        one = calls[4 * k: 4 * k + 4]
        assert len({s[3]["call"] for s in one}) == 1
        for (_, _, end, _), (_, start, _, _) in zip(one, one[1:]):
            assert end <= start
        batch, scenarios, agents = COUNTS[entry]
        for _, _, _, args in one:
            assert (args["batch"], args["policies"], args["scenarios"],
                    args["steps"], args["agents"]) == (
                        batch, len(alloc.policy_names()), scenarios, STEPS, agents)
    assert calls[0][3]["call"] != calls[4][3]["call"]


def test_make_spec_spans_and_return_arrays_ends_at_launch(tmp_path):
    specs = _specs()
    sweep_mod.sweep(paper_fleet(), specs, shard=False, return_arrays=True)

    def run():
        out = sweep_mod.sweep(paper_fleet(), _specs(), shard=False,
                              return_arrays=True)
        jax.block_until_ready(out)

    found = _traced(tmp_path, run)
    made = [s for s in found if s[0] == "workload.make_spec"]
    assert len(made) == len(specs)
    assert {s[3]["gen"] for s in made} == {"constant", "poisson", "spike", "diurnal",
                                           "bursty", "correlated"}
    calls = _call_spans(found, "sweep")
    assert [n for n, *_ in calls] == ["sweep.prepare", "sweep.launch"]


_SHARDED_CHILD = """
import importlib, json, pathlib, sys, tempfile
sys.path.insert(0, {tests!r})
import test_spans as t
from repro.core.agents import synthetic_fleet
sweep_mod = importlib.import_module("repro.core.sweep")
fleets = [synthetic_fleet(4, seed=i) for i in range(3)]
run = lambda: sweep_mod.sweep_fleets(fleets, num_steps=t.STEPS, synthesize=True)
run()
found = t._call_spans(t._traced(pathlib.Path(tempfile.mkdtemp()), run), "sweep_fleets")
print(json.dumps([[n, a["batch"]] for n, _, _, a in found]))
"""


def test_sharded_sweep_runs_as_four_phase_spans():
    """Three fleets on four forced host devices: the padded ``shard_map``
    path, whose padding is part of ``prepare``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD.format(tests=str(HERE))],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    found = json.loads(proc.stdout.strip().splitlines()[-1])
    assert found == [[f"sweep.{p}", 3] for p in PHASES]


def test_phases_tile_a_call_and_close_on_error(tmp_path):
    def run():
        with spans.Phases("unit", "a", entry="test") as ph:
            ph.annotate(size=3)
            ph.enter("b")
        with pytest.raises(RuntimeError):
            with spans.Phases("unit", "c"):
                raise RuntimeError("boom")

    found = [s for s in _traced(tmp_path, run) if s[0].startswith("unit.")]
    assert [n for n, *_ in found] == ["unit.a", "unit.b", "unit.c"]
    assert found[0][2] <= found[1][1]
    assert found[0][3]["size"] == found[1][3]["size"] == 3
    assert found[0][3]["call"] == found[1][3]["call"] != found[2][3]["call"]


def _scopes(lowered) -> set[str]:
    """Every name-stack path of the lowered program's ops."""
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]*)"', text))


def _kernel_text(**kw) -> set[str]:
    fleet = paper_fleet()
    wspec = workload.stack_specs(_specs())
    return _scopes(sweep_mod._stream_grid_jit.lower(
        None, fleet, None, kw.get("capacity"), wspec, kw.get("failures"),
        config=SimConfig(), names=alloc.policy_names(), batch_axis=None,
        block_size=kw.get("block_size", 1),
    ))


def _has(paths, *parts) -> bool:
    """Whether some op's scope path holds ``parts`` as consecutive names."""
    k = len(parts)
    for path in paths:
        names = path.split("/")
        if any(tuple(names[i:i + k]) == parts for i in range(len(names) - k + 1)):
            return True
    return False


@pytest.mark.parametrize("block_size", [1, 8])
def test_streaming_kernel_carries_stage_scopes(block_size):
    paths = _kernel_text(block_size=block_size)
    for stage in ("synthesis", "policies", "queue", "accumulate"):
        assert _has(paths, stage), stage
    for name in alloc.policy_names():
        assert _has(paths, "policies", name), name
    assert not _has(paths, "capacity") and not _has(paths, "failures")


def test_capacity_and_failure_stages_carry_scopes():
    paths = _kernel_text(capacity=capacity_config("reactive", min_instances=1.0),
                         failures=fail_mod.failure_scenario_library()[1])
    assert _has(paths, "capacity") and _has(paths, "failures")
