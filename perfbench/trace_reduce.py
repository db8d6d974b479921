"""From a jax.profiler capture to the numbers the per-layer readers use.

    python3 perfbench/trace_reduce.py <trace dir> <out.json> [<kernel name> ...]

(The further names are a configuration's own "kernels", summed beside the
four of KERNELS; contract: docstring of perfbench/run.py.)

Two stages, so that the second can be checked on a small recorded trace
(tests/perfbench/data/): `extract` reads the `.xplane.pb` with nothing but
JAX's own ProfileData and keeps the device planes' lines (name, start and
duration in nanoseconds, a few stats) plus the host's `polykey/` trace
annotations; `reduce` turns those events into seconds. run.py starts this
as a process of its own, pinned to the CPU, after the server has exited.

What `reduce` returns (seconds are averaged over the device planes, i.e.
over the chips used):
  window_s            first start to last end of any device operation
  busy_s              union of the intervals in which an operation ran
  modules             per jitted program ("jit__decode_fn", ...): count,
                      total_s, durations_s, gaps_s (device gap between
                      consecutive executions of the same program)
  ops                 per "<program>/<operation>": total_s, count — leaf
                      operations only (an operation that contains others,
                      such as a while loop, is left out of the sums)
  kernels             per kernel name (KERNELS, then the names given): an
                      operation counts under the first name it starts
                      with: total_s, count
  collective_s        time in collective operations
  collective_exposed_s  the part of it with no other operation running
  device_ops          the ten largest entries of `ops`
  idle_gaps           the ten longest idle gaps, each named by the host
                      annotation that overlaps it ("no_annotation" if none)
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KEPT_STATS = ("hlo_module", "hlo_op", "program_id", "run_id", "hlo_category",
              "long_name", "tf_op", "kernel_details", "flops",
              "bytes_accessed")
KERNELS = ("paged_attention_decode", "flash_attention", "paged_kv_write",
           "ragged_paged_attention")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)
ANNOTATION_PREFIX = "polykey/"
HLO_TEXT = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def short_name(name: str) -> str:
    """'%copy.48 = bf16[4096,8,8,128]{...} copy(...)' -> 'copy.48 bf16[4096,8,8,128]':
    the TPU's op events carry the whole HLO instruction as their name."""
    m = HLO_TEXT.match(name)
    if not m:
        return name[:120]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(xplane_path: str) -> dict:
    """Device planes' events and host annotations, as plain lists."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes, annotations, structure = [], [], []
    for plane in data.planes:
        device = plane.name.startswith("/device:") and "TPU" in plane.name
        summary = {"plane": plane.name, "lines": []}
        lines = []
        for line in plane.lines:
            count = 0
            events = []
            stat_names = set()
            for event in line.events:
                count += 1
                if device:
                    stats = {}
                    for key, value in event.stats:
                        if count <= 3:
                            stat_names.add(key)
                        if key in KEPT_STATS and isinstance(
                                value, (str, int, float)):
                            stats[key] = value
                    events.append([short_name(event.name), event.start_ns,
                                   event.duration_ns, stats])
                elif event.name.startswith(ANNOTATION_PREFIX):
                    annotations.append([event.name, event.start_ns,
                                        event.duration_ns])
            summary["lines"].append(
                {"line": line.name, "events": count,
                 "stats": sorted(stat_names)})
            if device:
                lines.append({"name": line.name, "events": events})
        structure.append(summary)
        if device:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "annotations": annotations,
            "structure": structure}


def union_s(intervals) -> float:
    """Total length of the union of [start, end) intervals (ns -> s)."""
    total, end = 0.0, None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total / 1e9


def gaps(intervals) -> list:
    """The idle stretches between the merged intervals: (start, end)."""
    out, end = [], None
    for start, stop in sorted(intervals):
        if end is not None and start > end:
            out.append((end, start))
        end = stop if end is None else max(end, stop)
    return out


def program_of(name: str) -> str:
    """'jit__decode_fn(123)' -> 'jit__decode_fn'."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def leaves(events: list) -> list:
    """Events that contain no other event of the line (start order)."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    keep = []
    for i, event in enumerate(ordered):
        end = event[1] + event[2]
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < end and nxt[1] + nxt[2] <= end \
                and nxt[2] < event[2]:
            continue
        keep.append(event)
    return keep


def _owner(modules: list, start: float) -> str:
    """The program whose execution covers `start` (modules sorted)."""
    lo, hi = 0, len(modules) - 1
    while lo <= hi:
        mid = (lo + hi) // 2
        m = modules[mid]
        if start < m[1]:
            hi = mid - 1
        elif start >= m[1] + m[2]:
            lo = mid + 1
        else:
            return program_of(m[0])
    return "no_program"


def reduce_plane(plane: dict, kernels: tuple = KERNELS) -> dict:
    by_line = {line["name"]: line["events"] for line in plane["lines"]}
    ops = by_line.get(OPS_LINE, [])
    modules = sorted(by_line.get(MODULES_LINE, []), key=lambda e: e[1])
    if not ops:
        return {}
    spans = [(e[1], e[1] + e[2]) for e in ops]
    out = {
        "window": (min(s for s, _ in spans), max(e for _, e in spans)),
        "busy_s": union_s(spans),
        "gaps": gaps(spans),
        "modules": {}, "ops": {}, "kernels": {},
    }
    last_end = {}
    for name, start, dur, _ in modules:
        prog = program_of(name)
        entry = out["modules"].setdefault(
            prog, {"count": 0, "total_s": 0.0, "durations_s": [], "gaps_s": []})
        entry["count"] += 1
        entry["total_s"] += dur / 1e9
        entry["durations_s"].append(dur / 1e9)
        if prog in last_end:
            entry["gaps_s"].append(max(0.0, (start - last_end[prog]) / 1e9))
        last_end[prog] = start + dur
    collective, others = [], []
    for event in leaves(ops):
        name, start, dur, stats = event
        prog = stats.get("hlo_module") or _owner(modules, start)
        key = f"{prog}/{name}"
        entry = out["ops"].setdefault(key, {"total_s": 0.0, "count": 0})
        entry["total_s"] += dur / 1e9
        entry["count"] += 1
        for kernel in kernels:
            if name.startswith(kernel):
                k = out["kernels"].setdefault(
                    kernel, {"total_s": 0.0, "count": 0, "by_program": {}})
                k["total_s"] += dur / 1e9
                k["count"] += 1
                k["by_program"][prog] = k["by_program"].get(prog, 0.0) + dur / 1e9
                break
        (collective if COLLECTIVE.search(name) else others).append(
            (start, start + dur))
    out["collective_s"] = union_s(collective)
    both = union_s(collective + others)
    out["collective_exposed_s"] = both - union_s(others)
    return out


def name_gap(gap, annotations: list) -> str:
    best, overlap = "no_annotation", 0.0
    for name, start, dur in annotations:
        got = min(gap[1], start + dur) - max(gap[0], start)
        if got > overlap:
            best, overlap = name, got
    return best


def reduce(extracted: dict, more_kernels=()) -> dict:
    kernels = KERNELS + tuple(k for k in more_kernels if k not in KERNELS)
    planes = [p for p in (reduce_plane(plane, kernels)
                          for plane in extracted["planes"]) if p]
    if not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "planes": 0}
    n = len(planes)
    start = min(p["window"][0] for p in planes)
    end = max(p["window"][1] for p in planes)

    def merged(field: str, numeric: tuple) -> dict:
        total: dict = {}
        for p in planes:
            for key, entry in p[field].items():
                into = total.setdefault(key, {})
                for k, v in entry.items():
                    if k in numeric:
                        into[k] = into.get(k, 0) + v / n
                    elif isinstance(v, list):
                        into.setdefault(k, [])
                        if p is planes[0]:
                            into[k] = v
                    elif isinstance(v, dict):
                        sub = into.setdefault(k, {})
                        for kk, vv in v.items():
                            sub[kk] = sub.get(kk, 0) + vv / n
        return total

    ops = merged("ops", ("total_s", "count"))
    all_gaps = sorted((g for p in planes[:1] for g in p["gaps"]),
                      key=lambda g: g[0] - g[1])[:10]
    return {
        "planes": n,
        "window_s": (end - start) / 1e9,
        "busy_s": sum(p["busy_s"] for p in planes) / n,
        "modules": merged("modules", ("count", "total_s")),
        "ops": ops,
        "kernels": merged("kernels", ("total_s", "count")),
        "collective_s": sum(p["collective_s"] for p in planes) / n,
        "collective_exposed_s":
            sum(p["collective_exposed_s"] for p in planes) / n,
        "device_ops": [[k, v["total_s"]] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1]["total_s"])[:10]],
        "idle_gaps": [[name_gap(g, extracted["annotations"]),
                       (g[1] - g[0]) / 1e9] for g in all_gaps],
    }


def main(argv) -> int:
    trace_dir, out_path = argv[1], argv[2]
    extracted = extract(find_xplane(trace_dir))
    with gzip.open(re.sub(r"\.json$", "", out_path) + ".events.json.gz",
                   "wt") as f:
        json.dump(extracted, f)
    reduced = reduce(extracted, argv[3:])
    reduced["structure"] = extracted["structure"]
    with open(out_path, "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
