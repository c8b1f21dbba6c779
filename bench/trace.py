"""Reduce a profiler trace (``.xplane.pb``) to device busy, idle and op times.

The JAX profiler writes one plane per device (``/device:TPU:<n>``) whose
``XLA Ops`` line holds every operation the device ran, and host planes whose
lines hold what the host threads did (``TraceMe`` and ``TraceAnnotation``
events). From those this module derives:

* busy time: the union of a device's op intervals, averaged over devices;
* per-op time, under a stable name (the op's name without its numeric
  suffix), summed over the window and averaged over devices; ops that only
  hold other ops (``while``, ``conditional``, ``call``) are not counted
  again;
* collective time not overlapped by compute on the same device;
* idle gaps between busy intervals, each labelled by the innermost host
  event that spans its middle.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
#: ops that only contain other ops (their bodies are traced as ops of their own)
CONTAINER = re.compile(r"\s(while|conditional|call)\(")
CUSTOM_TARGET = re.compile(r'custom_call_target="([^"]+)"')
#: host events too generic to say what the host was doing
GENERIC_HOST = re.compile(r"^(ThreadpoolListener|\$|end_|start_)")


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def stable_name(text: str) -> str:
    """An op's name without the numeric suffix the compiler appends.

    TPU traces name an op by its HLO text (``%fusion.12 = f32[...] fusion(...)``);
    the name is the part before ``=``, and a custom call also carries its
    target (``closed_call:tpu_custom_call``).
    """
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    name = re.sub(r"(\.\d+)+$", "", head)
    target = CUSTOM_TARGET.search(text)
    return f"{name}:{target.group(1)}" if target else name


def is_container(text: str) -> bool:
    return bool(CONTAINER.search(text))


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> float:
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


@dataclass
class RawTrace:
    """Device op events per device, program executions, and host events."""

    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: List[Event]


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path) -> RawTrace:
    """Read the planes this reduction needs from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: Dict[int, List[Event]] = defaultdict(list)
    modules: Dict[int, List[Event]] = defaultdict(list)
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] += [Event(e.name, e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[dev] += [Event(e.name, e.start_ns, e.duration_ns)
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns, e.duration_ns) for e in line.events
                         if e.duration_ns > 0 and not GENERIC_HOST.match(e.name)]
    return RawTrace(dict(ops), dict(modules), host)


@dataclass
class TraceSummary:
    raw: RawTrace
    window_s: float

    @property
    def devices(self) -> List[int]:
        return sorted(self.raw.ops) or sorted(self.raw.modules)

    def _device_events(self, dev: int) -> List[Event]:
        return self.raw.ops.get(dev) or self.raw.modules.get(dev, [])

    def busy_intervals(self, dev: int) -> List[Tuple[float, float]]:
        return merge((e.start_ns, e.end_ns) for e in self._device_events(dev))

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices traced."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(covered(self.busy_intervals(d)) for d in devs) / len(devs) / 1e9

    @property
    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.devices:
            return None
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per stable op name, averaged over devices."""
        out: Dict[str, float] = defaultdict(float)
        devs = self.devices
        for d in devs:
            for e in self.raw.ops.get(d, []):
                if not is_container(e.name):
                    out[stable_name(e.name)] += e.dur_ns / 1e9 / len(devs)
        return dict(out)

    def events(self, match: Callable[[Event], bool]) -> List[Event]:
        """Device op events (all devices) that ``match``."""
        return [e for d in self.devices for e in self.raw.ops.get(d, []) if match(e)]

    def module_events(self, match: Callable[[str], bool]) -> List[Event]:
        return [e for d in self.devices for e in self.raw.modules.get(d, []) if match(e.name)]

    def collective_exposed_s(self) -> float:
        """Collective time with no other op running on that device, averaged."""
        devs = self.devices
        if not devs:
            return 0.0
        total = 0.0
        for d in devs:
            evs = self.raw.ops.get(d, [])
            kind = [COLLECTIVE.match(stable_name(e.name)) is not None for e in evs]
            coll = merge((e.start_ns, e.end_ns) for e, c in zip(evs, kind) if c)
            comp = merge((e.start_ns, e.end_ns) for e, c in zip(evs, kind)
                         if not c and not is_container(e.name))
            total += subtract(coll, comp)
        return total / len(devs) / 1e9

    def idle_gaps(self, top: int = 10) -> List[List[object]]:
        """Idle seconds between busy intervals, summed by what the host did."""
        by_label: Dict[str, float] = defaultdict(float)
        host = sorted(self.raw.host, key=lambda e: e.start_ns)
        devs = self.devices
        for d in devs:
            busy = self.busy_intervals(d)
            gaps = sorted(((e0 + s1) / 2, s1 - e0) for (_, e0), (s1, _) in zip(busy, busy[1:]))
            active: List[Event] = []
            i = 0
            for mid, length in gaps:  # sweep: host events that span each gap's middle
                while i < len(host) and host[i].start_ns <= mid:
                    active.append(host[i])
                    i += 1
                active = [h for h in active if h.end_ns >= mid]
                label = min(active, key=lambda h: h.dur_ns).name if active else "(no host event)"
                by_label[label] += length / 1e9 / len(devs)
        ranked = sorted(by_label.items(), key=lambda kv: kv[1], reverse=True)
        return [[k, v] for k, v in ranked[:top]]

    def breakdown(self, top: int = 10) -> Dict[str, List[List[object]]]:
        ops = sorted(self.op_seconds().items(), key=lambda kv: kv[1], reverse=True)
        return {"device_ops": [[k, v] for k, v in ops[:top]], "idle_gaps": self.idle_gaps(top)}


def summarize(path: Path, *, window_s: float, chips: int = 1) -> TraceSummary:
    raw = load(path)
    if chips and len(raw.ops) > chips:
        keep = sorted(raw.ops)[:chips]
        raw = RawTrace({d: raw.ops[d] for d in keep},
                       {d: v for d, v in raw.modules.items() if d in keep}, raw.host)
    return TraceSummary(raw, window_s)
