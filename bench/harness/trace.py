"""Reduction of a profiler trace to device time, idle gaps and spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  A device plane is one whose name
starts with ``/device:`` and is not the host CPU.  On a device plane the
``XLA Modules`` line holds one event per launch of a compiled program
(its name is the module's, with a ``(<id>)`` suffix) and the ``XLA Ops``
line one event per operation; busy time is the union of the operation
intervals (of the module intervals where a plane has no op line).  Host
spans are the harness's own annotations (``bench.*``) on the host
planes, on the same clock.  The traced window is the ``bench.window``
span.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from .stats import union_seconds

WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                          # mean over device planes
    modules: dict[str, list]               # name -> [launches, seconds]
    ops: dict[str, float]                  # op name -> device seconds
    idle_by_host: dict[str, float]         # host activity -> idle seconds
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, names) -> tuple[int, float] | None:
        """(launches, seconds) summed over the modules named; None when
        the trace holds none of them."""
        hits = [self.modules[n] for n in names if n in self.modules]
        if not hits:
            return None
        return sum(h[0] for h in hits), sum(h[1] for h in hits)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules: dict[str, list] = {}
    ops: dict[str, float] = {}
    busy_per_dev: list[list] = []
    host: list[tuple[float, float, str]] = []
    window = None
    for plane in pd.planes:
        if _is_device(plane.name):
            op_iv, mod_iv = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        name = _SUFFIX.sub("", ev.name)
                        slot = modules.setdefault(name, [0, 0.0])
                        slot[0] += 1
                        slot[1] += ev.duration_ns * 1e-9
                        mod_iv.append((ev.start_ns, ev.start_ns
                                       + ev.duration_ns))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        ops[ev.name] = ops.get(ev.name, 0.0) \
                            + ev.duration_ns * 1e-9
                        op_iv.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns))
            iv = op_iv or mod_iv
            if iv:
                busy_per_dev.append(iv)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        span = (ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name)
                        if ev.name == WINDOW_SPAN:
                            window = span
                        else:
                            host.append(span)
    if window is None:
        raise RuntimeError(f"trace {path} has no {WINDOW_SPAN} span")
    w0, w1 = window[0], window[1]
    busy = [union_seconds((max(s, w0), min(e, w1)) for s, e in iv
                          if e > w0 and s < w1) * 1e-9
            for iv in busy_per_dev]
    idle = _idle_by_host(busy_per_dev, host, w0, w1)
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / len(busy) if busy else 0.0,
                   modules=modules, ops=ops, idle_by_host=idle,
                   n_devices=len(busy_per_dev))


def _idle_by_host(busy_per_dev, host, w0, w1) -> dict[str, float]:
    """Idle device seconds within the window, split by the harness span
    the host was in (``between events`` outside every span); averaged
    over devices."""
    if not busy_per_dev:
        return {}
    spans = sorted(host)
    out: dict[str, float] = {}
    for iv in busy_per_dev:
        gaps = []
        cur = w0
        for s, e in _merge((max(s, w0), min(e, w1)) for s, e in iv
                           if e > w0 and s < w1):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < w1:
            gaps.append((cur, w1))
        j = 0
        for g0, g1 in gaps:
            covered = 0.0
            while j < len(spans) and spans[j][1] <= g0:
                j += 1
            k = j
            while k < len(spans) and spans[k][0] < g1:
                s0, s1, name = spans[k]
                ov = min(s1, g1) - max(s0, g0)
                if ov > 0:
                    key = name[len("bench."):]
                    out[key] = out.get(key, 0.0) + ov * 1e-9
                    covered += ov
                k += 1
            rest = (g1 - g0) - covered
            if rest > 0:
                out["between events"] = out.get("between events", 0.0) \
                    + rest * 1e-9
    n = len(busy_per_dev)
    return {k: v / n for k, v in out.items()}
