"""Shares of the traced window by the names the program gives its phases,
shared by the readers beside this file.

* **Device shares.** ``jax.named_scope`` puts a phase's name (``cola.mix``,
  ``cola.local_solve``, ``cola.record``, ...) into the HLO ``op_name`` of
  every op traced inside it; the device trace carries it as the op's
  ``tf_op``. A share is the seconds of the device's leaf ops whose
  ``tf_op`` holds the scope as a path component, over the part of the
  window that the device's op line covers, in percent, the mean over
  devices. A leaf op encloses no other op event on its line, so a
  ``while`` or ``cond`` and the ops inside it count once.
* **Idle shares.** ``repro.obs.trace.span`` opens a host annotation for
  each host phase (``env-build``, ``stop-sync``, ...). An idle share is the
  device's idle gaps (``Device.gaps``) intersected with the union of the
  named host spans, over the part of the window the device's trace covers
  (``Device.window_s``), in percent, the mean over devices. A union: the
  Python tracer's frames nested in the spans change nothing.

``tf_op`` sits in the device plane's event metadata, which
``jax.profiler.ProfileData`` does not expose, so the trace's ``XSpace`` is
read here, with the few messages it needs declared on the installed
``protobuf``. Op times are kept in picoseconds, as recorded, and moved
onto the host's clock as ``trace_reduce.summarize`` moves them.

A reader returns None where the program names no phase: no op carries a
``cola.`` scope, or no span of the set lies in the window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys
import time
from collections import defaultdict

import numpy as np

from bench import harness, trace_reduce

SOLVE, UPDATE, RECORD = "cola.local_solve", "cola.update", "cola.record"
# every device scope of the program, in the order the coverage line lists
# them; cola.exchange nests in cola.mix, cola.grad or cola.record
SCOPES = ("cola.mix", "cola.exchange", "cola.grad", SOLVE, UPDATE, RECORD,
          "cola.schedule", "obs.counters")
PROGRAM_PREFIXES = ("cola.", "obs.")
SETUP_SPANS = ("env-build", "recorder-setup", "schedule-build",
               "driver-build")
BLOCK_SPANS = ("block-first-dispatch", "block-dispatch", "stop-sync",
               "history-fetch")
TF_OP = "tf_op"

# (message, [(field, number, type)]): a type in _SCALARS is a scalar, any
# other names a message and makes the field repeated. A proto map is a
# repeated (key = 1, value = 2) message on the wire, so the metadata maps
# are declared as such.
_XPLANE = (
    ("XSpace", [("planes", 1, "XPlane")]),
    ("XPlane", [("name", 2, "string"), ("lines", 3, "XLine"),
                ("event_metadata", 4, "EventMetadataEntry"),
                ("stat_metadata", 5, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, "int64"),
                            ("value", 2, "XEventMetadata!")]),
    ("StatMetadataEntry", [("key", 1, "int64"),
                           ("value", 2, "XStatMetadata!")]),
    ("XLine", [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
               ("events", 4, "XEvent")]),
    ("XEvent", [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
                ("duration_ps", 3, "int64")]),
    ("XEventMetadata", [("id", 1, "int64"), ("name", 2, "string"),
                        ("display_name", 4, "string"),
                        ("stats", 5, "XStat")]),
    ("XStat", [("metadata_id", 1, "int64"), ("str_value", 5, "string"),
               ("ref_value", 7, "uint64")]),
    ("XStatMetadata", [("id", 1, "int64"), ("name", 2, "string")]),
)
_SCALARS = {"string": 9, "int64": 3, "uint64": 4}
_CLASSES: dict = {}


def xspace_class():
    """The ``XSpace`` message class, declared once (``tsl`` xplane.proto's
    field numbers; unread fields are skipped)."""
    if not _CLASSES:
        from google.protobuf import (descriptor_pb2, descriptor_pool,
                                     message_factory)
        proto = descriptor_pb2.FileDescriptorProto(
            name="bench_xplane.proto", package="bench_xplane",
            syntax="proto3")
        for msg, fields in _XPLANE:
            desc = proto.message_type.add(name=msg)
            for field, number, kind in fields:
                f = desc.field.add(name=field, number=number)
                if kind in _SCALARS:
                    f.type, f.label = _SCALARS[kind], 1       # optional
                else:   # a message: repeated, or singular with a "!"
                    f.type, f.label = 11, 1 if kind.endswith("!") else 3
                    f.type_name = ".bench_xplane." + kind.rstrip("!")
        pool = descriptor_pool.DescriptorPool()
        pool.Add(proto)
        _CLASSES["XSpace"] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _CLASSES["XSpace"]


def read_space(path: str):
    space = xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


@dataclasses.dataclass
class Ops:
    """One device's op line: event times in ps on the device's clock."""
    start: np.ndarray     # int64
    end: np.ndarray       # int64
    meta: np.ndarray      # int64, the event's metadata id
    tf_op: dict           # metadata id -> tf_op ("" where it has none)
    name: dict            # metadata id -> op name
    modules: list         # [(start_ns, end_ns, name)] of the module line


def _tf_ops(plane) -> tuple:
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    tf_op, name = {}, {}
    for entry in plane.event_metadata:
        md = entry.value
        name[entry.key] = md.display_name or trace_reduce.op_name(md.name)
        tf_op[entry.key] = ""
        for st in md.stats:
            if stat_names.get(st.metadata_id) == TF_OP:
                tf_op[entry.key] = (st.str_value if st.str_value
                                    else stat_names.get(st.ref_value, ""))
    return tf_op, name


def read_ops(space, devices: list) -> tuple:
    """({device plane name: Ops}, host enqueue starts in ns) of an
    ``XSpace``; ``devices``: the plane names to read."""
    by_dev, enqueues = {}, []
    for plane in space.planes:
        if plane.name in devices:
            tf_op, name = _tf_ops(plane)
            lines = {line.name: line for line in plane.lines}
            ops = lines.get(trace_reduce.OPS_LINE)
            evs = [] if ops is None else ops.events
            start = np.fromiter((e.offset_ps for e in evs), np.int64,
                                len(evs))
            dur = np.fromiter((e.duration_ps for e in evs), np.int64,
                              len(evs))
            meta = np.fromiter((e.metadata_id for e in evs), np.int64,
                               len(evs))
            if ops is not None:
                start += ops.timestamp_ns * 1000
            mods = lines.get(trace_reduce.MODULES_LINE)
            modules = [] if mods is None else [
                (mods.timestamp_ns + e.offset_ps // 1000,
                 mods.timestamp_ns + e.offset_ps // 1000
                 + e.duration_ps // 1000, "") for e in mods.events]
            by_dev[plane.name] = Ops(start, start + dur, meta, tf_op, name,
                                     modules)
        elif plane.name.startswith("/host:"):
            ids = {e.key for e in plane.event_metadata
                   if e.value.name == trace_reduce.ENQUEUE_SPAN}
            for line in plane.lines if ids else ():
                enqueues.extend(line.timestamp_ns + e.offset_ps // 1000
                                for e in line.events if e.metadata_id in ids)
    return by_dev, sorted(enqueues)


def host_shift_ps(ops: Ops, enqueues: list) -> int:
    """The shift ``trace_reduce.shift_to_host`` gives this device's events,
    in ps (0 where it leaves them as recorded)."""
    if not ops.modules:
        return 0
    shifted = trace_reduce.shift_to_host([], ops.modules, enqueues)[1]
    return 1000 * (min(s for s, _, _ in shifted)
                   - min(s for s, _, _ in ops.modules))


def leaf_mask(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """True for each event that encloses no other event of the line. On a
    line of nested events, an event encloses another exactly when the next
    event in (start, longest first) order lies inside it."""
    order = np.lexsort((-end, start))
    s, e = start[order], end[order]
    encloses = np.zeros(len(s), bool)
    encloses[:-1] = (s[1:] < e[:-1]) & (e[1:] <= e[:-1])
    leaf = np.empty(len(s), bool)
    leaf[order] = ~encloses
    return leaf


def leaf_seconds(ops: Ops, lo_ps: int, hi_ps: int) -> dict:
    """{metadata id: seconds} of the leaf ops, clipped to [lo_ps, hi_ps)."""
    leaf = leaf_mask(ops.start, ops.end)
    start = np.maximum(ops.start[leaf], lo_ps)
    end = np.minimum(ops.end[leaf], hi_ps)
    keep = end > start
    meta, secs = ops.meta[leaf][keep], (end - start)[keep] * 1e-12
    ids, inverse = np.unique(meta, return_inverse=True)
    return dict(zip(ids.tolist(), np.bincount(inverse, secs).tolist()))


def components(tf_op: str) -> list:
    """The path of ``tf_op`` (``jit(f)/while/body/cola.mix/dot_general:``)
    as its components."""
    return [c.rstrip(":") for c in tf_op.split("/")]


def outside(tf_op: str) -> bool:
    """``tf_op`` lies outside every scope of the program."""
    return not any(c.startswith(PROGRAM_PREFIXES) for c in components(tf_op))


@dataclasses.dataclass
class DeviceScopes:
    name: str
    covered_s: float      # the part of the window the op line covers
    by_tf_op: dict        # tf_op -> leaf-op seconds
    op_names: dict        # tf_op -> {op name}

    def seconds(self, scope: str) -> float:
        return sum(s for op, s in self.by_tf_op.items()
                   if scope in components(op))

    def outside_s(self) -> float:
        return sum(s for op, s in self.by_tf_op.items() if outside(op))

    def named(self) -> bool:
        return any(c.startswith("cola.") for op in self.by_tf_op
                   for c in components(op))


def device_scopes(summary, ops: Ops, shift_ps: int, dev) -> DeviceScopes:
    """Leaf-op seconds by ``tf_op`` of ``dev`` (a ``trace_reduce.Device``
    of ``summary``), over the part of its window its op line covers: up to
    its last op where a ``bench.solve`` starts after it (the line was cut
    short), else the whole of ``dev.window``."""
    lo, hi = (t * 1000 for t in dev.window)
    if len(ops.end):
        last = int(ops.end.max()) + shift_ps
        solves = [s * 1000 for s, _, n in summary.host_spans
                  if n == trace_reduce.SOLVE_SPAN]
        if any(s > last for s in solves):
            hi = min(hi, last)
    per_id = leaf_seconds(ops, lo - shift_ps, hi - shift_ps)
    by_tf_op, op_names = defaultdict(float), defaultdict(set)
    for mid, secs in per_id.items():
        op = ops.tf_op.get(mid, "")
        by_tf_op[op] += secs
        op_names[op].add(ops.name.get(mid, str(mid)))
    return DeviceScopes(dev.name, max(hi - lo, 0) * 1e-12, dict(by_tf_op),
                        dict(op_names))


def coverage_lines(dev: DeviceScopes, busy_s: float) -> list:
    """The check that the scopes cover the round: leaf-op seconds outside
    every program scope, and each scope's top five ``tf_op``s."""
    total = sum(dev.by_tf_op.values())
    out = dev.outside_s()
    lines = [f"scopes {dev.name}: leaf ops {total:.6f} s in the "
             f"{dev.covered_s:.6f} s its op line covers; outside every "
             f"program scope {out:.6f} s "
             f"({100.0 * out / busy_s if busy_s else 0.0:.4f}% of busy "
             f"{busy_s:.6f} s)"]
    for scope in SCOPES + ("outside",):
        ops = sorted(((s, op) for op, s in dev.by_tf_op.items()
                      if (outside(op) if scope == "outside"
                          else scope in components(op))), reverse=True)
        if ops:
            lines.append(f"  {scope} {sum(s for s, _ in ops):.6f} s")
            lines += [f"    {s:.6f} s {op or '(no tf_op)'} "
                      f"[{' '.join(sorted(dev.op_names[op])[:4])}]"
                      for s, op in ops[:5]]
    return lines


def trace_file() -> str | None:
    """The newest ``*.xplane.pb`` under the benchmark's trace directory."""
    found = glob.glob(os.path.join(str(harness.TRACE_DIR), "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


_CACHE: dict = {}


def scopes_of(run) -> list | None:
    """[DeviceScopes] of the run's traced devices, read once per trace and
    shared by the readers; the coverage lines go to standard error."""
    if run.trace is None or not run.trace.devices:
        return None
    path = trace_file()
    if path is None:
        return None
    key = (path, os.stat(path).st_mtime_ns,
           tuple(d.window for d in run.trace.devices))
    if key not in _CACHE:
        t0 = time.perf_counter()
        names = [d.name for d in run.trace.devices]
        by_dev, enqueues = read_ops(read_space(path), names)
        print(f"scopes: {os.path.getsize(path)} bytes of XSpace read in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
        found = []
        for dev in run.trace.devices:
            ops = by_dev.get(dev.name)
            if ops is None:
                continue
            shift = (host_shift_ps(ops, enqueues) if len(names) == 1
                     else 0)
            scopes = device_scopes(run.trace, ops, shift, dev)
            for line in coverage_lines(scopes, dev.busy_s):
                print(line, file=sys.stderr)
            found.append(scopes)
        _CACHE.clear()
        _CACHE[key] = found if any(d.named() for d in found) else None
    return _CACHE[key]


def device_share(run, scope: str) -> float | None:
    """Leaf-op seconds in ``scope`` over the covered window, %, mean over
    devices."""
    devs = scopes_of(run)
    if not devs:
        return None
    shares = [d.seconds(scope) / d.covered_s for d in devs if d.covered_s]
    return 100.0 * sum(shares) / len(shares) if shares else None


def overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted lists of disjoint
    [(start, end)] intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in_spans(summary, names) -> float | None:
    """The device's idle time inside the union of the host spans named
    ``names``, over ``Device.window_s``, %, mean over devices; None where
    no such span lies in the window."""
    if summary is None or not summary.devices:
        return None
    spans = trace_reduce.merge([(s, e) for s, e, n in summary.host_spans
                                if n in names])
    if not spans:
        return None
    shares = []
    for dev in summary.devices:
        inside = trace_reduce.clip(spans, *dev.window)
        shares.append(overlap_ns(dev.gaps, inside) * 1e-9 / dev.window_s)
        by_name = []
        for name in names:
            one = trace_reduce.clip(trace_reduce.merge(
                [(s, e) for s, e, n in summary.host_spans if n == name]),
                *dev.window)
            by_name.append(f"{name} {len(one)}x "
                           f"{overlap_ns(dev.gaps, one) * 1e-9:.6f} s")
        print(f"idle in spans {dev.name}: " + ", ".join(by_name),
              file=sys.stderr)
    return 100.0 * sum(shares) / len(shares)
