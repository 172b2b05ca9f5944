"""Where the chip's idle and busy time go, by the program's own names.

The program marks its phases with host spans ``repro:<name>``
(``src/repro/core/spans.py``): each call of a sweep entry point is four
back-to-back spans ``sweep.prepare``, ``sweep.launch``, ``sweep.wait`` and
``sweep.collect``, and ``workload.make_spec`` marks the spec building a
caller does before it.  The streaming kernel names the stages of its scan
step with ``jax.named_scope`` (``synthesis``, ``policies/<name>``,
``capacity``, ``failures``, ``queue``, ``accumulate``); on the chip the
scope path of each op is the ``SCOPE_STAT`` stat of its metadata.

Idle: a chip's idle time in the window (the complement of the union of its
``XLA Ops`` intervals, as ``Traced.idle_share`` counts it) is cut exactly
at the program spans' edges, and each piece goes to the innermost program
span over it: ``prepare`` (``sweep.prepare`` and ``workload.make_spec``),
``launch``, ``wait``, ``collect``, or ``unnamed`` under none.  The five
shares, averaged over the chips, sum to the idle share.

Busy: the device time of the grid program's leaf ops (an op whose interval
holds no other op of its line, so a ``while`` and the body ops inside it
do not count twice) whose scope path holds a stage's name.  The TPU's
profiler keeps an op's scope path in the ``tf_op`` stat of the op's
metadata, which ``jax.profiler.ProfileData`` does not expose, so
``op_scopes`` reads it from the trace file's ``XSpace`` protobuf itself.

Every function returns ``None`` when the trace has nothing to read: a
program without these spans or scopes.
"""
from __future__ import annotations

import gzip
import os

import numpy as np

PREFIX = "repro:"
SCOPE_STAT = "tf_op"
PHASE_OF = {
    "sweep.prepare": "prepare",
    "workload.make_spec": "prepare",
    "sweep.launch": "launch",
    "sweep.wait": "wait",
    "sweep.collect": "collect",
}
LABELS = ("prepare", "launch", "wait", "collect", "unnamed")


def program_spans(events) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of every ``repro:`` span on the host plane."""
    out = []
    for evs in events.planes.get("/host:CPU", {}).values():
        for name, s, d, _ in evs:
            if name.startswith(PREFIX):
                out.append((name[len(PREFIX):], s, s + d))
    return out


def merged_busy(ops, lo: float, hi: float) -> tuple[np.ndarray, np.ndarray]:
    """The union of the ops' intervals clipped to [lo, hi], as sorted
    disjoint (starts, ends)."""
    st = np.fromiter((o[1] for o in ops), float, len(ops))
    en = st + np.fromiter((o[2] for o in ops), float, len(ops))
    st, en = np.maximum(st, lo), np.minimum(en, hi)
    keep = en > st
    st, en = st[keep], en[keep]
    if not len(st):
        return st, en
    order = np.argsort(st, kind="stable")
    st, en = st[order], en[order]
    reach = np.maximum.accumulate(en)
    first = np.ones(len(st), bool)
    first[1:] = st[1:] > reach[:-1]
    idx = np.flatnonzero(first)
    return st[idx], np.maximum.reduceat(en, idx)


def busy_before(starts, ends, x: np.ndarray) -> np.ndarray:
    """Busy time of the disjoint intervals before each point of ``x``."""
    if not len(starts):
        return np.zeros(len(x))
    done = np.concatenate([[0.0], np.cumsum(ends - starts)])
    k = np.searchsorted(starts, x, side="right")
    over = np.where(k > 0, np.maximum(ends[np.maximum(k - 1, 0)] - x, 0.0), 0.0)
    return done[k] - over


def idle_by_label(busy, labelled, lo: float, hi: float, default: str) -> dict[str, float]:
    """Idle ns of one chip in [lo, hi], cut at the edges of the
    ``labelled`` spans (start, end, label) and summed by the label of the
    first span in that list over each piece (``default`` under none);
    ``busy`` is ``merged_busy``'s pair."""
    edges = np.unique(np.clip([lo, hi] + [x for s, e, _ in labelled for x in (s, e)],
                              lo, hi))
    idle = np.diff(edges) - np.diff(busy_before(*busy, edges))
    out = {}
    for a, b, ns in zip(edges[:-1], edges[1:], idle):
        mid = 0.5 * (a + b)
        label = next((lab for s, e, lab in labelled if s <= mid <= e), default)
        out[label] = out.get(label, 0.0) + float(ns)
    return out


def innermost_first(spans) -> list[tuple[float, float, str]]:
    """(start, end, label) of ``spans`` (label, start, end), shortest
    first: the first that covers a point is the innermost there."""
    return sorted(((s, e, n) for n, s, e in spans), key=lambda t: t[1] - t[0])


def idle_shares(run) -> dict[str, float] | None:
    """Percent of the window per label, averaged over the cell's chips."""
    cache = run.setdefault("_program_spans", {})
    if "idle" not in cache:
        cache["idle"] = _idle_shares(run["trace"], [d.id for d in run["devices"]])
    return cache["idle"]


def _idle_shares(trace, device_ids):
    lo, hi = trace.window_bounds()
    spans = [(n, s, e) for n, s, e in program_spans(trace.events)
             if n in PHASE_OF and e > lo and s < hi]
    planes = trace.events.device_planes(device_ids)
    if not spans or not planes or hi <= lo:
        return None
    labelled = innermost_first((PHASE_OF[n], s, e) for n, s, e in spans)
    total = dict.fromkeys(LABELS, 0.0)
    for plane in planes:
        busy = merged_busy(trace.ops(plane), lo, hi)
        for label, ns in idle_by_label(busy, labelled, lo, hi, "unnamed").items():
            total[label] += ns
    return {k: 100.0 * v / len(planes) / (hi - lo) for k, v in total.items()}


def idle_share(run, label: str) -> float | None:
    shares = idle_shares(run)
    return None if shares is None else shares[label]


def wait_us_per_step(run) -> float | None:
    """Host-clock µs in ``sweep.wait`` spans in the window, per scan step."""
    trace, steps = run["trace"], run["records"]["scan_steps"]
    lo, hi = trace.window_bounds()
    ns = sum(min(e, hi) - max(s, lo) for n, s, e in program_spans(trace.events)
             if n == "sweep.wait" and e > lo and s < hi)
    if ns <= 0 or not steps:
        return None
    return ns / 1e3 / steps


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field, value) of each field of one protobuf message in
    ``buf[i:end]``; a length-delimited value is its (start, end)."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} in a trace file")
        yield key >> 3, value


def op_scopes(path: str) -> dict[str, dict[str, str]]:
    """For each device plane of the ``.xplane.pb`` (or ``.pb.gz``) at
    ``path``: op name -> the ``SCOPE_STAT`` stat of the op's metadata.

    ``XSpace.planes`` (1) -> ``XPlane``: ``name`` (2), ``event_metadata``
    (4, map entries whose value (2) is an ``XEventMetadata``: ``name`` (2),
    ``stats`` (5)) and ``stat_metadata`` (5, map entries whose value (2)
    is an ``XStatMetadata``: ``id`` (1), ``name`` (2)).  An ``XStat`` holds
    ``metadata_id`` (1) and a string in ``str_value`` (5)."""
    if not os.path.exists(path):
        return {}
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    text = lambda span: buf[span[0]:span[1]].decode("utf-8", "replace")
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(buf, *plane):
            if pf == 2:
                name = text(pv)
            elif pf == 4:
                events.extend(v for f, v in _fields(buf, *pv) if f == 2)
            elif pf == 5:
                for f, v in _fields(buf, *pv):
                    meta = dict(_fields(buf, *v)) if f == 2 else {}
                    if 2 in meta:
                        stat_names[meta.get(1, 0)] = text(meta[2])
        if not name.startswith("/device:"):
            continue
        want = {k for k, v in stat_names.items() if v == SCOPE_STAT}
        scopes = {}
        for ev in events:
            op, scope = None, None
            for mf, mv in _fields(buf, *ev):
                if mf == 2:
                    op = text(mv)
                elif mf == 5:
                    stat = dict(_fields(buf, *mv))
                    if stat.get(1, 0) in want and isinstance(stat.get(5), tuple):
                        scope = text(stat[5])
            if op is not None and scope is not None:
                scopes[op] = scope
        out[name] = scopes
    return out


def stage_ns(run) -> dict[str, float] | None:
    """Device ns of the grid program's leaf ops by the stage names in
    their scope paths, per chip, averaged over the cell's chips."""
    cache = run.setdefault("_program_spans", {})
    if "stages" not in cache:
        cache["stages"] = _stage_ns(run)
    return cache["stages"]


STAGES = ("synthesis", "policies", "capacity", "failures", "queue", "accumulate")


def leaf_ns(ops, windows) -> dict[str, float]:
    """Device ns by op name of the ops that start inside one of
    ``windows`` (sorted disjoint (start, end)) and hold no other op."""
    st = np.fromiter((o[1] for o in ops), float, len(ops))
    du = np.fromiter((o[2] for o in ops), float, len(ops))
    starts = np.asarray([a for a, _ in windows])
    ends = np.asarray([b for _, b in windows])
    k = np.searchsorted(starts, st, side="right") - 1
    idx = np.flatnonzero((k >= 0) & (st < ends[np.maximum(k, 0)]))
    order = idx[np.lexsort((-du[idx], st[idx]))]
    st, du = st[order], du[order]
    leaf = np.ones(len(order), bool)
    leaf[:-1] = st[1:] >= st[:-1] + du[:-1]
    out = {}
    for i, d in zip(order[leaf].tolist(), du[leaf].tolist()):
        name = ops[i][0]
        out[name] = out.get(name, 0.0) + d
    return out


def _stage_ns(run):
    trace, prefixes = run["trace"], tuple(run["records"]["programs"])
    lo, hi = trace.window_bounds()
    planes = trace.events.device_planes([d.id for d in run["devices"]])
    from_file = op_scopes(trace.path)
    total = dict.fromkeys(STAGES, 0.0)
    for plane in planes:
        mods = sorted((s, s + d) for n, s, d, _ in trace.events.modules(plane)
                      if n.startswith(prefixes) and lo <= s < hi)
        if not mods:
            continue
        scopes = from_file.get(plane, {})
        for name, ns in leaf_ns(trace.ops(plane), mods).items():
            path = scopes.get(name, "").split("/")
            for stage in STAGES:
                if stage in path:
                    total[stage] += ns
    if not planes or not any(total.values()):
        return None
    return {k: v / len(planes) for k, v in total.items()}


def stage_us_per_step(run, stage: str) -> float | None:
    ns, steps = stage_ns(run), run["records"]["scan_steps"]
    if ns is None or not steps:
        return None
    return ns[stage] / 1e3 / steps
