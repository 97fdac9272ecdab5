"""Divide the chip's idle time among the serving program's own spans.

The program opens a ``jax.profiler.TraceAnnotation`` named ``serve.<name>``
at each layer boundary (``repro.obs.trace.span``), so a profiler trace
holds the host's phases on the device's clock. This pass:

- reads every ``serve.*`` event on every line of the ``/host:CPU`` plane,
  whatever the line's name;
- takes the batcher worker's line: the one that holds most ``serve.linger``
  and ``serve.wait`` events;
- intersects each idle interval of each device (a gap between the union of
  its ``XLA Ops`` intervals, as ``trace.reduce`` takes them) exactly with
  the innermost worker span open over each part of it, by time overlap;
  idle time under no worker span is unattributed;
- sums the device time of the stage-1 executable's module events
  (``jit_serve_stage1`` on the ``XLA Modules`` line) and the host time of
  the ``serve.stage1`` spans.

Device times are averaged over the devices, as ``trace.reduce`` averages
busy time. ``shares`` gives each as % of the traced seconds, taken as the
device's own extent in the trace (first op's start to last op's end), so
the idle shares sum to the trace's idle share exactly. A trace of a
program without the spans gives no worker line: ``reduce`` then returns
``{"worker_spans": 0}`` and ``shares`` nothing.
``chipbench/tools/idle_spans.py`` prints both for a kept trace.
"""
from __future__ import annotations

import collections

from chipbench.trace import _union

PREFIX = "serve."
WORKER_MARKS = ("linger", "wait")
STAGE1_MODULE = "jit_serve_stage1"
# span groups whose idle time is reported: each span with the children
# that run inside it on the worker's thread. ``stage2`` runs on the hedge
# pool's thread, and inside ``dispatch`` on the worker only when the pool
# is exhausted and the hedged run goes inline
GROUPS = {"linger": ("linger",),
          "stage1": ("stage1", "stage1_wait"),
          "pack": ("pack", "fill", "h2d"),
          "dispatch": ("dispatch", "stage2"),
          "unpack": ("unpack",)}


def innermost(spans: list[tuple[int, int, str]]
              ) -> list[tuple[int, int, str]]:
    """Disjoint, time-ordered ``(start, end, name)`` pieces of the time
    that ``spans`` cover, each named for the innermost span open over it:
    of the spans open there, the one that started last (on a tie, the
    shorter)."""
    evs = []
    for k, (s, e, _) in enumerate(spans):
        if e > s:
            evs.append((s, 1, -e, k))
            evs.append((e, 0, 0, k))
    evs.sort()
    out: list[tuple[int, int, str]] = []
    stack: list[int] = []
    prev = None
    for t, opens, _, k in evs:
        if stack and t > prev:
            out.append((prev, t, spans[stack[-1]][2]))
        if opens:
            stack.append(k)
        elif stack[-1] == k:
            stack.pop()
        else:
            stack.remove(k)
        prev = t
    return out


def divide(gaps: list[tuple[int, int]],
           pieces: list[tuple[int, int, str]]
           ) -> tuple[collections.Counter, int]:
    """Idle time of ``gaps`` (disjoint, sorted) under each span name of
    ``pieces`` (``innermost``'s output), and the idle time under none."""
    under: collections.Counter = collections.Counter()
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            s, e, name = pieces[k]
            under[name] += min(e, ge) - max(s, gs)
            k += 1
    return under, sum(e - s for s, e in gaps) - sum(under.values())


def reduce(path: str) -> dict:
    """Reduce one trace file (seconds throughout)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    worker: list[tuple[int, int, str]] = []
    span_ns: collections.Counter = collections.Counter()
    span_calls: collections.Counter = collections.Counter()
    devices: list[tuple[list, int]] = []     # (busy union, stage-1 ns)
    for plane in data.planes:
        if plane.name == "/host:CPU":
            marks = 0
            for line in plane.lines:
                spans = [(e.start_ns, e.end_ns, e.name[len(PREFIX):])
                         for e in line.events if e.name.startswith(PREFIX)]
                for s, e, name in spans:
                    span_ns[name] += e - s
                    span_calls[name] += 1
                n = sum(name in WORKER_MARKS for _, _, name in spans)
                if n > marks:
                    worker, marks = spans, n
        elif plane.name.startswith("/device:TPU:"):
            ops, stage1 = [], 0
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.end_ns) for e in line.events)
                elif line.name == "XLA Modules":
                    stage1 += sum(e.duration_ns for e in line.events
                                  if e.name.startswith(STAGE1_MODULE))
            merged = _union(ops)
            if merged:
                devices.append((merged, stage1))
    if not worker or not devices:
        return {"worker_spans": 0}
    pieces = innermost(worker)
    by_span: collections.Counter = collections.Counter()
    unattributed = idle = extent = stage1_ns = 0
    for merged, stage1 in devices:
        gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        under, none = divide(gaps, pieces)
        by_span.update(under)
        unattributed += none
        idle += sum(e - s for s, e in gaps)
        extent += merged[-1][1] - merged[0][0]
        stage1_ns += stage1
    n = len(devices)
    return {
        "worker_spans": len(worker),
        "extent_s": extent / n / 1e9,
        "idle_s": idle / n / 1e9,
        "idle_s_by_span": {k: v / n / 1e9 for k, v in by_span.items()},
        "idle_unattributed_s": unattributed / n / 1e9,
        "idle_in_s": {g: sum(by_span[m] for m in members) / n / 1e9
                      for g, members in GROUPS.items()},
        "span_s": {k: v / 1e9 for k, v in span_ns.items()},
        "span_calls": dict(span_calls),
        "stage1_device_s": stage1_ns / n / 1e9,
    }


def shares(red: dict) -> dict:
    """The reduction's idle times as % of the traced seconds (the
    device's extent), beside the trace's whole idle share, and the
    stage-1 executable's device time as % of the ``serve.stage1`` spans'
    host time (None without stage-1 spans); empty where the trace holds
    no worker spans."""
    if not red.get("worker_spans"):
        return {}
    traced = red["extent_s"]
    out = {"idle": 100.0 * red["idle_s"] / traced}
    out.update({f"idle_in_{g}": 100.0 * s / traced
                for g, s in red["idle_in_s"].items()})
    out["idle_unattributed"] = 100.0 * red["idle_unattributed_s"] / traced
    s1 = red["span_s"].get("stage1", 0.0)
    out["stage1_device_share"] = (100.0 * red["stage1_device_s"] / s1
                                  if s1 else None)
    return out

