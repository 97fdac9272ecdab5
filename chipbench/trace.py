"""Reduce a JAX profiler trace (``.xplane.pb``) to the device metrics the
benchmark reports.

- busy: the union of the intervals in which an operation ran on each
  device (the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane),
  averaged over the devices;
- per kernel: the summed device time of its events and the least time the
  chip could take for the same work (``counts.py``, from the
  configuration's widths and the rows each event shows);
- the device operations that took most time, and the idle gaps between
  device operations grouped by what the host's Python threads were doing
  at the gap's midpoint (``breakdown``).

An XLA op event is named by its HLO text, for example
``%gather_einsum_kernel.2 = f32[100,4096,80]{...} custom-call(f32[4096,18]
%x, ...)``: the instruction name, the output shape and the operand shapes
are read from it.
"""
from __future__ import annotations

import collections
import glob
import heapq
import os
import re

from chipbench import counts

_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
# instruction-name prefix -> kernel name in BENCHMARK.json's metrics
KERNELS = {"mari_matmul_kernel": "mari_matmul",
           "gather_einsum_kernel": "gather_einsum"}


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def parse_op(name: str) -> tuple[str, tuple | None, list]:
    """(instruction name without its ``.N`` suffix, output shape, operand
    shapes) of an HLO-text event name; shapes are (dtype, dims)."""
    head, _, rest = name.partition(" = ")
    inst = head.lstrip("%").split(".")[0].strip()
    shapes = [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
              for m in _SHAPE.finditer(rest.split("custom_call_target")[0])]
    if not shapes:
        return inst, None, []
    return inst, shapes[0], shapes[1:]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _mari_matmul_work(out, operands, sites):
    """(flops, bytes) of one mari_matmul event against the configuration's
    (layer, K, N) sites, or None when no site fits. A site fits when its K
    equals the event's K or rounds up to it by the kernel's 512-deep K
    tiles, and its N equals the event's N or rounds up to it by the
    kernel's N tile (128 up to N = 128, else 256); of several fitting
    sites the one with the least work is taken."""
    if out is None or len(out[1]) != 2:
        return None
    rows, n_ev = out[1]
    two_d = [d for dt, d in operands if dt.startswith("f") and len(d) == 2]
    w = [d for d in two_d if d[1] == n_ev
         and any(x == (rows, d[0]) for x in two_d)]
    if not w:
        return None
    k_ev = w[0][0]
    table = [d for dt, d in operands if dt.startswith("f") and len(d) == 3]
    gathered = bool(table)
    init_rows = table[0][0] if table else rows

    def up(v, m):
        return -(-v // m) * m

    fits = [(k, n) for _, k, n in sites
            if (k == k_ev or up(k, 512) == k_ev)
            and (n == n_ev or n_ev == up(n, 128 if n <= 128 else 256))]
    if not fits:
        return None
    return min(counts.mari_matmul(rows, k, n, init_rows, gathered)
               for k, n in fits)


def _gather_einsum_work(out, operands, sites):
    """(flops, bytes) of one gather_einsum event against the configuration's
    (layer, L, D, H) sites, or None when its table fits none."""
    floats = [d for dt, d in operands if dt.startswith("f")]
    tables = [d for d in floats if len(d) in (3, 4)]
    xs = [d for d in floats if len(d) == 2]
    if not tables or not xs:
        return None
    table, rows = tables[0], xs[0][0]
    for _, seq, d, h in sites:
        if table[1:] == (seq, d, h):
            return counts.gather_einsum(rows, table[0], seq, d, h)
        if table[1:] == (seq, d):
            return counts.gather_einsum(rows, table[0], seq, d, None)
    return None


def reduce(path: str, sites: dict, peak: dict, top: int = 10) -> dict:
    """Reduce one trace file. ``sites`` is the reference module's
    ``kernel_sites(cfg)``; ``peak`` one device's row of ``peaks.json``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    busy_ns: list[int] = []
    gaps: list[tuple[int, int]] = []
    op_time: collections.Counter = collections.Counter()
    kernels: dict[str, dict] = {}
    host: list[tuple[int, int, str]] = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend((e.start_ns, e.end_ns, e.name)
                                for e in line.events)
            continue
        if not plane.name.startswith("/device:TPU:"):
            continue
        spans = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                spans.append((e.start_ns, e.end_ns))
                inst, out, operands = parse_op(e.name)
                shape = (f"{out[0]}[{','.join(map(str, out[1]))}]"
                         if out else "")
                op_time[f"{inst} {shape}".strip()] += e.duration_ns
                kernel = next((k for p, k in KERNELS.items()
                               if inst.startswith(p)), None)
                if kernel is None:
                    continue
                kr = kernels.setdefault(kernel, {"seconds": 0.0,
                                                 "min_seconds": 0.0,
                                                 "events": 0,
                                                 "unmatched": 0})
                kr["seconds"] += e.duration_ns / 1e9
                kr["events"] += 1
                work = (_mari_matmul_work(out, operands,
                                          sites.get("mari_matmul", []))
                        if kernel == "mari_matmul" else
                        _gather_einsum_work(out, operands,
                                            sites.get("gather_einsum", [])))
                if work is None:
                    kr["unmatched"] += 1
                else:
                    kr["min_seconds"] += counts.min_seconds(*work, peak)
        merged = _union(spans)
        if merged:
            busy_ns.append(sum(e - s for s, e in merged))
            gaps.extend((a[1], b[0]) for a, b in zip(merged, merged[1:]))
    if not busy_ns:
        return {"devices": 0}

    # sweep the gaps' midpoints in order over the host events sorted by
    # start, keeping the events open at the midpoint in a heap by end
    host.sort()
    idle: collections.Counter = collections.Counter()
    active: list[tuple[int, int, str]] = []
    i = 0
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (s + e) // 2
        while i < len(host) and host[i][0] <= mid:
            hs, he, name = host[i]
            heapq.heappush(active, (he, hs, name))
            i += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        what = (min((he - hs, name) for he, hs, name in active)[1][:80]
                if active else "no host event")
        idle[what] += e - s
    return {
        "devices": len(busy_ns),
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "kernels": kernels,
        "device_ops": [[n, t / 1e9] for n, t in op_time.most_common(top)],
        "idle_gaps": [[n, t / 1e9] for n, t in idle.most_common(top)],
    }
