"""The reduction from a profiler trace to device events, busy time, idle
gaps and what the host was doing in them.

``Tracer`` records one window with JAX's profiler (the Python tracer off,
so tracing costs the host little) and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``.  What it reads:

* device planes: ``/device:TPU:<id>``; their line ``XLA Ops`` holds one
  event per operation run (fusions, custom calls such as the Pallas
  kernels, copies), their line ``XLA Modules`` one event per program run
  (``jit_<name>(...)``);
* the host plane ``/host:CPU``: the benchmark's own spans, written with
  ``span(name)`` as ``chipbench:<name>``.

Busy time is the union of the ``XLA Ops`` intervals of a chip; the idle
share is one minus busy over the traced window.  The idle time is cut at
the edges of the host spans, and each piece is named by the innermost of
the program's own spans over it (``repro:<name>``,
``chipbench/program_spans.py``), or else by the innermost benchmark span.
"""
from __future__ import annotations

import collections
import glob
import time
from pathlib import Path

from chipbench import program_spans as ps

SPAN_PREFIX = "chipbench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def span(name: str):
    """A host span in the profiler's trace; free when nothing traces."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def merge(intervals):
    """Union of (start, end) intervals, as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Events:
    """The events of one trace file, by plane and line."""

    def __init__(self, planes: dict):
        # planes[name][line] = [(name, start_ns, dur_ns, stats), ...]
        self.planes = planes

    @classmethod
    def from_file(cls, path: str) -> "Events":
        """Reads an ``.xplane.pb``, or one compressed as ``.xplane.pb.gz``."""
        from jax.profiler import ProfileData

        if str(path).endswith(".gz"):
            import gzip

            with gzip.open(path, "rb") as f:
                data = ProfileData.from_serialized_xspace(f.read())
        else:
            data = ProfileData.from_file(path)
        planes = {}
        for plane in data.planes:
            if not (plane.name.startswith("/device:") or plane.name == "/host:CPU"):
                continue
            lines = {}
            for line in plane.lines:
                evs = []
                for e in line.events:
                    stats = dict(e.stats) if plane.name != "/host:CPU" else {}
                    evs.append((e.name, float(e.start_ns), float(e.duration_ns), stats))
                lines.setdefault(line.name, []).extend(evs)
            planes[plane.name] = lines
        return cls(planes)

    def device_planes(self, ids=None) -> list[str]:
        names = sorted(n for n in self.planes if n.startswith("/device:TPU:")
                       and n.rsplit(":", 1)[-1].isdigit())
        if ids is None:
            return names
        want = {f"/device:TPU:{i}" for i in ids}
        return [n for n in names if n in want]

    def ops(self, plane: str) -> list:
        return self.planes.get(plane, {}).get(OPS_LINE, [])

    def modules(self, plane: str) -> list:
        return self.planes.get(plane, {}).get(MODULES_LINE, [])

    def host_spans(self) -> list:
        out = []
        for evs in self.planes.get("/host:CPU", {}).values():
            out.extend((n[len(SPAN_PREFIX):], s, d) for n, s, d, _ in evs
                       if n.startswith(SPAN_PREFIX))
        return out


class Traced:
    """One traced window: its events and its length on the host clock."""

    def __init__(self, events: Events, window_s: float, path: str):
        self.events = events
        self.window_s = window_s
        self.path = path

    def window_bounds(self) -> tuple[float, float]:
        spans = [(s, s + d) for n, s, d in self.events.host_spans() if n == "window"]
        if spans:
            return spans[0]
        starts, ends = [], []
        for plane in self.events.device_planes():
            for _, s, d, _ in self.ops(plane):
                starts.append(s)
                ends.append(s + d)
        return (min(starts), max(ends)) if starts else (0.0, 0.0)

    def ops(self, plane: str) -> list:
        return self.events.ops(plane)

    def busy_ns(self, plane: str) -> float:
        lo, hi = self.window_bounds()
        ivs = [(max(s, lo), min(s + d, hi)) for _, s, d, _ in self.ops(plane)]
        return sum(e - s for s, e in merge(iv for iv in ivs if iv[1] > iv[0]))

    def idle_share(self, device_ids):
        """Percent of the window in which no operation ran, averaged over
        the chips ``device_ids``; ``None`` when the trace has none."""
        planes = self.events.device_planes(device_ids)
        lo, hi = self.window_bounds()
        if not planes or hi <= lo:
            return None
        busy = sum(self.busy_ns(p) for p in planes) / len(planes)
        return 100.0 * (1.0 - busy / (hi - lo))

    def summary(self, device_ids) -> dict:
        planes = self.events.device_planes(device_ids)
        lo, hi = self.window_bounds()
        window_s = (hi - lo) / 1e9
        busy = [self.busy_ns(p) / 1e9 for p in planes]
        busy_s = sum(busy) / len(busy) if busy else 0.0
        per_op = collections.Counter()
        for p in planes:
            for name, s, d, _ in self.ops(p):
                if s + d > lo and s < hi:
                    per_op[name] += d / 1e9 / len(planes)
        return {
            "busy_s": busy_s,
            "window_s": window_s,
            "breakdown": {
                "device_ops": [[n, v] for n, v in per_op.most_common(10)],
                "idle_gaps": self.idle_gaps(planes[0], lo, hi)[:10] if planes else [],
            },
        }

    def idle_gaps(self, plane: str, lo: float, hi: float) -> list:
        """Idle seconds of ``plane`` inside [lo, hi] by what the host was
        doing: the innermost program span (``repro:<name>``) over each
        piece, else the innermost benchmark span; longest first."""
        program = ps.innermost_first((ps.PREFIX + n, s, e)
                                     for n, s, e in ps.program_spans(self.events))
        ours = ps.innermost_first((n, s, s + d) for n, s, d in self.events.host_spans()
                                  if n != "window")
        busy = ps.merged_busy(self.ops(plane), lo, hi)
        gaps = ps.idle_by_label(busy, program + ours, lo, hi, "outside any span")
        return [[n, v / 1e9] for n, v in sorted(gaps.items(), key=lambda kv: -kv[1])
                if v > 0]


class Tracer:
    """Records the profiler's trace of one window into ``directory``."""

    def __init__(self, directory: Path):
        self.directory = Path(directory)
        self._span = None

    def start(self) -> None:
        import shutil

        import jax

        if self.directory.exists():
            shutil.rmtree(self.directory)
        self.directory.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)
        self._span = span("window")
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def stop(self) -> Traced:
        import jax

        window_s = time.perf_counter() - self._t0
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = glob.glob(str(self.directory / "plugins" / "profile" / "*" / "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.directory}")
        return Traced(Events.from_file(files[0]), window_s, files[0])
