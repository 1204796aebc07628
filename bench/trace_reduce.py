"""From a profiler trace to device busy and idle time, per-op time and idle
gaps labelled by what the host was doing.

A trace is an XSpace (``*.xplane.pb``), read with ``jax.profiler.ProfileData``.
Device planes are named ``/device:TPU:<n>``. Their ``XLA Modules`` line holds
one event per program the device ran, and their ``XLA Ops`` line one per
operation, named by its HLO text (``%fusion.3 = f32[...] fusion(...)``, read
as ``fusion.3``). The op line can come back incomplete: the profiler keeps
a bounded number of events, and a loop of small ops (the serial CD scan)
runs past it; the module line stays complete. Host planes hold the spans of
host threads: the benchmark's ``TraceAnnotation``s, JAX's own and, with the
Python tracer on, one per Python call.

* busy: the union of the device's module intervals inside the window (of
  its op intervals, where the trace has no module line);
* idle share: 1 - busy / window;
* per-op time: the summed durations of the recorded ops of one name inside
  the window;
* idle gaps: each interval of the window in which the device runs no op,
  labelled by the shortest host span that covers its midpoint (the deepest
  frame the host was in), and summed by label.

The device's clock in a trace runs apart from the host's (1.3 ms behind it
in the recorded v5e trace under ``tests/bench/data``). On one device, its
programs pair in order with the host's enqueues (``DoEnqueueProgram``), and
where every pair lies within ``PAIR_BAND`` of the others its events are
moved onto the host's clock by the least shift that starts no program
before its enqueue; otherwise, and on several devices, they stay as
recorded.

A device's event buffer is bounded: where a device's last event ends before
the host starts a ``bench.solve`` span, its trace was cut short, and the
window for that device's numbers ends at its last event (``covered``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SOLVE_SPAN = "bench.solve"
ENQUEUE_SPAN = "DoEnqueueProgram"
PAIR_BAND = 1_000_000     # ns
UNLABELLED = "(no host span)"


@dataclasses.dataclass
class Device:
    name: str
    busy_s: float
    op_s: dict            # op name -> seconds inside the window
    gaps: list            # [(start_ns, end_ns)] idle intervals
    events: tuple = (0, 0)  # (ops, modules) recorded in the window
    window: tuple = None    # (start_ns, end_ns) this device's numbers cover

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


@dataclasses.dataclass
class Summary:
    window: tuple         # (start_ns, end_ns): the host's bench.window
    devices: list         # [Device], by plane name
    host_spans: list      # [(start_ns, end_ns, name)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def covered(self, device: Device) -> float:
        """Share of the window that ``device``'s trace covers."""
        return device.window_s / self.window_s

    def idle_share(self, device: Device) -> float:
        """1 - busy / the part of the window the device's trace covers."""
        return 1.0 - device.busy_s / device.window_s

    def top_ops(self, count: int = 10) -> list:
        """The ops that took most device time, summed over devices."""
        total = defaultdict(float)
        for dev in self.devices:
            for name, secs in dev.op_s.items():
                total[name] += secs
        return sorted(total.items(), key=lambda kv: -kv[1])[:count]

    def op_share(self, device: Device, match) -> float:
        """Seconds of ``device``'s ops whose name ``match`` accepts, over the
        part of the window its trace covers."""
        secs = sum(s for name, s in device.op_s.items() if match(name))
        return secs / device.window_s

    def gap_labels(self, count: int = 10) -> list:
        """Idle seconds by the host span that held them, summed over
        devices, longest first."""
        spans = sorted(self.host_spans, key=lambda s: s[1] - s[0])
        total = defaultdict(float)
        for dev in self.devices:
            for start, end in dev.gaps:
                total[label_of((start + end) // 2, spans)] += (end - start) * 1e-9
        return sorted(total.items(), key=lambda kv: -kv[1])[:count]


def label_of(t_ns: int, spans_by_length: list) -> str:
    """The name of the shortest span that covers ``t_ns``."""
    for start, end, name in spans_by_length:
        if start <= t_ns < end and name != WINDOW_SPAN:
            return name
    return UNLABELLED


def merge(intervals: list) -> list:
    """Union of [(start, end)] as sorted disjoint intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(iv) for iv in out]


def clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[] fusion(...)`` -> ``fusion.3``."""
    head = hlo.split(" = ", 1)[0] if " = " in hlo else hlo
    return head.lstrip("%").strip()


def device_summary(name: str, ops: list, modules: list,
                   window: tuple) -> Device:
    """``ops``, ``modules``: [(start_ns, end_ns, name)] of one device."""
    lo, hi = window
    op_s = defaultdict(float)
    for start, end, op in ops:
        start, end = max(start, lo), min(end, hi)
        if end > start:
            op_s[op_name(op)] += (end - start) * 1e-9
    busy = merge(clip([(s, e) for s, e, _ in (modules or ops)], lo, hi))
    gaps, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < hi:
        gaps.append((cursor, hi))
    inside = tuple(sum(1 for s, e, _ in evs if e > lo and s < hi)
                   for evs in (ops, modules))
    return Device(name=name, busy_s=sum(e - s for s, e in busy) * 1e-9,
                  op_s=dict(op_s), gaps=gaps, events=inside, window=window)


def _events(line) -> list:
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in line.events]


def read_events(profile) -> tuple:
    """({plane name: (ops, modules)} of the devices, host spans) of a
    ``ProfileData``."""
    devices, host = {}, []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: _events(line) for line in plane.lines}
            devices[plane.name] = (lines.get(OPS_LINE, []),
                                   lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return devices, host


def shift_to_host(ops: list, modules: list, enqueues: list) -> tuple:
    """(ops, modules) of one device moved onto the host's clock: its k-th
    program pairs with the host's k-th enqueue (see the module
    docstring)."""
    starts = sorted(s for s, _, _ in modules)
    if not starts or len(starts) > len(enqueues):
        return ops, modules
    pairs = [e - m for e, m in zip(enqueues, starts)]
    if max(pairs) - min(pairs) > PAIR_BAND:
        return ops, modules
    shift = max(pairs)
    return ([(s + shift, e + shift, n) for s, e, n in ops],
            [(s + shift, e + shift, n) for s, e, n in modules])


def _extent(events: list):
    """(first start, last end) of [(start, end, name)], or None."""
    if not events:
        return None
    return (min(s for s, _, _ in events), max(e for _, e, _ in events))


def summarize(profile, devices: list | None = None) -> Summary:
    """Reduce a ``ProfileData``. The window is the host span
    ``bench.window``; without one, the extent of the device ops.
    ``devices``: plane names to keep (default: every device plane with
    ops)."""
    by_dev, host = read_events(profile)
    if devices is None:
        devices = sorted(name for name, (ops, mods) in by_dev.items()
                         if ops or mods)
    if not devices:
        raise ValueError("the trace holds no device plane")
    if len(devices) == 1:
        enqueues = sorted(s for s, _, name in host if name == ENQUEUE_SPAN)
        ops, mods = by_dev.get(devices[0], ([], []))
        by_dev = {devices[0]: shift_to_host(ops, mods, enqueues)}
    marks = [(s, e) for s, e, name in host if name == WINDOW_SPAN]
    spans = [span for name in devices for evs in by_dev.get(name, ([], []))
             for span in (_extent(evs),) if span is not None]
    if marks:
        window = (min(s for s, _ in marks), max(e for _, e in marks))
    elif spans:
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    else:
        raise ValueError("the trace holds no device op and no window span")
    solves = [s for s, _, name in host if name == SOLVE_SPAN]
    summaries = []
    for name in devices:
        ops, mods = by_dev.get(name, ([], []))
        ends = [span[1] for span in map(_extent, (ops, mods)) if span]
        last = max(ends, default=window[0])
        cut = any(s > last for s in solves)
        summaries.append(device_summary(
            name, ops, mods, (window[0], min(last, window[1]) if cut
                              else window[1])))
    return Summary(window=window, devices=summaries,
                   host_spans=[sp for sp in host
                               if sp[1] > window[0] and sp[0] < window[1]])


def load(trace_dir: str):
    """The ``ProfileData`` of the newest ``*.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(found, key=os.path.getmtime))
