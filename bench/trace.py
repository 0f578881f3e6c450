"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

``events(path)`` reads an ``.xplane.pb`` with nothing but JAX and returns
two lists of ``(name, start_ns, end_ns, detail)``: the operations of each
device (the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane; ``detail``
is the op's long name, which carries its shapes) and the host's
``TraceAnnotation`` spans. Everything else here works on those lists, so the
reduction is tested on a small recorded trace and on made-up intervals
alike.

A device's clock is not the host's: on a TPU v5e its times read about 1.7 ms
early. ``events`` moves every device time onto the host's clock
(``clock_offset``), so that an idle gap is named by the host span that was
really active in it. Where the runs cannot be paired, the device's times stay
as they are and every host span inside the window is named ``unaligned``.
"""
from __future__ import annotations

import bisect
import glob
import os
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float, str]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"                     # one event per program run
HOST_PLANE = "/host:CPU"
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"    # host: a program run issued
DONE = "tpu::System::Execute=>Done"              # host: a program run seen done
UNALIGNED = "unaligned"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _detail(ev) -> str:
    for key, value in ev.stats:
        if key in ("long_name", "hlo_op", "tf_op"):
            return str(value)
    return ""


def clock_offset(runs: Sequence[Tuple[float, float]], launches: Sequence[float],
                 dones: Sequence[float]) -> float:
    """Nanoseconds to add to a device's times to put them on the host's clock.

    The k-th program run on the device (``runs``: its start and end on the
    device's clock) is the k-th the host launched and the k-th it saw done;
    it cannot start before its launch nor end after its done, so each run
    bounds the offset from both sides. The offset is the middle of the range
    that all runs leave."""
    if not runs or not (len(runs) == len(launches) == len(dones)):
        raise ValueError(f"cannot pair {len(runs)} device program runs with "
                         f"{len(launches)} launches and {len(dones)} dones")
    lo = max(l - a for (a, _), l in zip(runs, launches))
    hi = min(d - b for (_, b), d in zip(runs, dones))
    if lo > hi:
        raise ValueError(f"device and host clocks disagree: offset above "
                         f"{lo:.0f} ns and below {hi:.0f} ns")
    return (lo + hi) / 2


def events(path: str, annotations: Iterable[str]) -> Tuple[Dict[int, List[Event]], List[Event]]:
    """(device ops by device index, on the host's clock; host spans named in
    ``annotations``)."""
    from jax.profiler import ProfileData

    wanted = set(annotations)
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    runs: Dict[int, List[Tuple[float, float]]] = {}
    host: List[Event] = []
    launches, dones = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            ops = devices.setdefault(idx, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.name, ev.start_ns, ev.end_ns, _detail(ev))
                               for ev in line.events)
                elif line.name == MODULES_LINE:
                    runs.setdefault(idx, []).extend(
                        (ev.start_ns, ev.end_ns) for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host.append((ev.name, ev.start_ns, ev.end_ns, ""))
                    elif ev.name == LAUNCH:
                        launches.append(ev.start_ns)
                    elif ev.name == DONE:
                        dones.append(ev.start_ns)
    launches.sort()
    dones.sort()
    for idx, ops in devices.items():
        try:
            off = clock_offset(sorted(runs.get(idx, [])), launches, dones)
        except ValueError as e:
            print(f"trace: device {idx}: {e}; idle gaps are not named",
                  file=sys.stderr)
            host = [(n if n == "window" else UNALIGNED, a, b, d)
                    for n, a, b, d in host]
            continue
        ops[:] = sorted(((n, a + off, b + off, d) for n, a, b, d in ops),
                        key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return devices, host


def window_of(host: Sequence[Event], name: str = "window") -> Tuple[float, float]:
    spans = [e for e in host if e[0] == name]
    if not spans:
        raise ValueError(f"no host span named {name!r} in the trace")
    return spans[-1][1], spans[-1][2]


def union(ops: Sequence[Event], w0: float, w1: float) -> List[Tuple[float, float]]:
    """Merged intervals in which some op ran, clipped to [w0, w1]."""
    out: List[List[float]] = []
    for _, a, b, _ in sorted(ops, key=lambda e: e[1]):
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops: Sequence[Event], w0: float, w1: float) -> float:
    return sum(b - a for a, b in union(ops, w0, w1))


def op_totals(ops: Sequence[Event], w0: float, w1: float) -> List[Tuple[str, float]]:
    """Device seconds by op name inside the window, largest first."""
    tot: Dict[str, float] = defaultdict(float)
    for name, a, b, _ in ops:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            tot[name] += (b - a) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])


def matching(ops: Sequence[Event], needle: str, w0: float, w1: float) -> List[Event]:
    """Ops inside the window whose name or long name contains ``needle``."""
    return [e for e in ops if w0 <= e[1] and e[2] <= w1
            and (needle in e[0] or needle in e[3])]


def _active(host: Sequence[Event], starts: Sequence[float], t: float) -> str:
    """The innermost (latest-starting) host span covering time t; spans nest
    shallowly, so the walk back from the last span started stays short."""
    i = bisect.bisect_right(starts, t) - 1
    for e in host[max(0, i - 64): i + 1][::-1]:
        if e[2] >= t:
            return e[0]
    return "none"


def idle_gaps(ops: Sequence[Event], host: Sequence[Event], w0: float, w1: float,
              skip: Iterable[str] = ("window",)) -> List[Tuple[str, float]]:
    """Every idle stretch of the device inside [w0, w1], longest first, named
    by the host span active at its midpoint (``skip`` names spans that only
    frame the window)."""
    inner = [e for e in host if e[0] not in set(skip)]
    starts = [e[1] for e in inner]
    gaps, t = [], w0
    for a, b in union(ops, w0, w1) + [(w1, w1)]:
        if a > t:
            gaps.append((_active(inner, starts, (t + a) / 2), (a - t) * 1e-9))
        t = max(t, b)
    return sorted(gaps, key=lambda g: -g[1])


def reduce(devices: Dict[int, List[Event]], host: Sequence[Event],
           top: int = 10) -> Dict:
    """busy_s and window_s (averaged over the devices), the top device ops and
    the longest idle gaps of device 0, all inside the host's ``window`` span."""
    w0, w1 = window_of(host)
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy = [busy_ns(ops, w0, w1) * 1e-9 for ops in devices.values()]
    ops0 = devices[min(devices)]
    gaps = idle_gaps(ops0, host, w0, w1)
    by_name: Dict[str, float] = defaultdict(float)
    for name, s in gaps:
        by_name[name] += s
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "device_ops": [[n, s] for n, s in op_totals(ops0, w0, w1)[:top]],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
        "idle_by_span": dict(by_name),
        "w0": w0, "w1": w1,
    }
