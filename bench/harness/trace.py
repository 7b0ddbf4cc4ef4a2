"""From a profiler trace to the numbers the per-layer metrics read.

``Trace.from_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
(``jax.profiler.ProfileData``), keeps what the metrics need, and drops
the rest:

* the window: the host span named ``window_span`` that the harness puts
  around the traced calls;
* per chip (planes ``/device:TPU:<n>``): the operations of the
  ``XLA Ops`` line and the programs of the ``XLA Modules`` line, each as
  ``[name, start_ns, duration_ns, program]``, clipped to the window;
* the host's spans inside the window (``[name, start_ns, duration_ns]``),
  to say what the host was doing in each gap of the device.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)

Interval = Tuple[int, int]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered_ns(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def module_base(name: str) -> str:
    """``jit_decode_step_multi(123)`` -> ``jit_decode_step_multi``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


class Trace:
    def __init__(self, data: Dict):
        self.data = data
        self.start, self.end = data["window"]
        self.devices = data["devices"]
        self.host = data["host"]

    # ------------------------------------------------------------ reading
    @classmethod
    def from_dir(cls, log_dir: str, window_span: str) -> "Trace":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls(extract(ProfileData.from_file(paths[-1]), window_span))

    # ------------------------------------------------------------ numbers
    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def busy_ns(self, dev: int) -> int:
        """Union of the device's operation intervals."""
        return covered_ns((s, s + d) for _, s, d, _ in
                          self.devices[dev]["ops"])

    def busy_mean_s(self) -> float:
        return sum(self.busy_ns(i) for i in range(self.n_devices)) / (
            1e9 * self.n_devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_mean_s() / self.window_s

    def modules(self, dev: int, pred: Callable[[str], bool]):
        return [m for m in self.devices[dev]["modules"]
                if pred(module_base(m[0]))]

    def module_ns(self, dev: int, pred: Callable[[str], bool]) -> int:
        return sum(m[2] for m in self.modules(dev, pred))

    def ops_ns(self, dev: int, op_pred: Callable[[str], bool],
               module_pred: Optional[Callable[[str], bool]] = None) -> int:
        """Time covered by the matching operations, their intervals
        merged, so that nested or overlapping events count once."""
        return covered_ns((s, s + d) for n, s, d, mod in
                          self.devices[dev]["ops"]
                          if op_pred(n) and (module_pred is None
                                             or module_pred(module_base(mod))))

    def collective_ns(self, dev: int) -> int:
        return covered_ns((s, s + d) for n, s, d, _ in
                          self.devices[dev]["ops"] if COLLECTIVE.search(n))

    # ------------------------------------------------------------ breakdown
    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time (mean over chips,
        named ``program/op``), and the longest idle gaps of chip 0, each
        named by the innermost host span that covers most of it."""
        per: Dict[str, int] = defaultdict(int)
        for dev in self.devices:
            for n, _, d, mod in dev["ops"]:
                per[f"{module_base(mod)}/{n}"] += d
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        device_ops = [[k, v / 1e9 / self.n_devices] for k, v in ops]
        busy = union((s, s + d) for _, s, d, _ in self.devices[0]["ops"])
        gaps, prev = [], self.start
        for s, e in busy + [(self.end, self.end)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        idle = [[self.host_label(s, e), (e - s) / 1e9] for s, e in gaps[:top]]
        return {"device_ops": device_ops, "idle_gaps": idle}

    def host_label(self, s: int, e: int) -> str:
        """The shortest host span that covers at least half of [s, e);
        with none, the host was running Python outside the runtime."""
        best, best_len = "host Python, no runtime span", None
        for name, hs, hd in self.host:
            overlap = min(e, hs + hd) - max(s, hs)
            if overlap >= (e - s) / 2 and (best_len is None or hd < best_len):
                best, best_len = name, hd
        return best


def _stat(ev, key: str):
    try:
        return dict(ev.stats).get(key)
    except Exception:  # stats of an event may fail to decode
        return None


def extract(pd, window_span: str) -> Dict:
    """The plain structure ``Trace`` reads, from a ``ProfileData``."""
    window = None
    host: List[list] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == window_span:
                    window = (int(ev.start_ns), int(ev.end_ns))
    if window is None:
        raise ValueError(f"no host span {window_span!r} in the trace")
    lo, hi = window

    def clip(s, d):
        s, e = max(int(s), lo), min(int(s + d), hi)
        return (s, e - s) if e > s else None

    devices = []
    for plane in sorted((p for p in pd.planes if DEVICE_PLANE.match(p.name)),
                        key=lambda p: int(DEVICE_PLANE.match(p.name)[1])):
        lines = {line.name: line for line in plane.lines}
        modules = []
        for ev in lines[MODULES_LINE].events if MODULES_LINE in lines else []:
            c = clip(ev.start_ns, ev.duration_ns)
            if c:
                modules.append([ev.name, c[0], c[1], ev.name])
        spans = sorted((m[1], m[1] + m[2], m[0]) for m in modules)
        starts = [sp[0] for sp in spans]
        ops = []
        for ev in lines[OPS_LINE].events:
            c = clip(ev.start_ns, ev.duration_ns)
            if not c:
                continue
            mod = _stat(ev, "hlo_module") or _containing(spans, starts, c[0])
            ops.append([ev.name, c[0], c[1], mod or ""])
        devices.append({"name": plane.name, "ops": ops, "modules": modules})
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                c = clip(ev.start_ns, ev.duration_ns)
                if c and ev.name != window_span:
                    host.append([ev.name, c[0], c[1]])
    return {"window": [lo, hi], "devices": devices, "host": host}


def _containing(spans, starts, t: int) -> Optional[str]:
    """The program running at ``t`` (programs on one chip never overlap)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < spans[i][1]:
        return spans[i][2]
    return None
