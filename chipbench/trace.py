"""From a JAX profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into flat
events ``[kind, device, name, start_ns, duration_ns]``:

* ``op``: one execution of an HLO instruction on a TPU (line "XLA Ops");
  the name is the instruction's text, ``%softmax_2d.6 = f32[...] ...``;
* ``async``: an asynchronous op (line "Async XLA Ops": copies and the
  collectives that overlap compute);
* ``module``: one execution of a compiled program (line "XLA Modules"),
  named ``jit_serve_step(<fingerprint>)``;
* ``host``: one of the harness's own ``TraceAnnotation`` spans (``window``,
  ``request``, ``batch``, ``train_step``).

:class:`Trace` reduces them: the device-busy union inside the window,
per-program and per-kernel device time, collectives not hidden under
compute, and the breakdown of the result line.  The profiler puts host
and device events on one clock.
"""

from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict

ANNOTATIONS = ("window", "request", "batch", "train_step")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")

_INSTR = re.compile(r"%?([^\s=]+)")
_SUFFIX = re.compile(r"\.\d+$")


def instruction(op_name: str) -> str:
    """``%softmax_2d.6 = f32[...] custom-call(...)`` -> ``softmax_2d.6``."""
    m = _INSTR.match(op_name)
    return m.group(1) if m else op_name


def base(name: str) -> str:
    """An instruction or program name without its numeric suffix or
    fingerprint: ``softmax_2d.6`` -> ``softmax_2d``,
    ``jit_serve_step(1077...)`` -> ``jit_serve_step``."""
    return _SUFFIX.sub("", name.split("(")[0])


def load(log_dir: str) -> list:
    """Flat events of the one ``.xplane.pb`` under ``log_dir``."""
    import jax

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device = plane.name[len("/device:"):]
            for line in plane.lines:
                kind = {"XLA Ops": "op", "Async XLA Ops": "async",
                        "XLA Modules": "module"}.get(line.name)
                if kind:
                    out.extend([kind, device, e.name, e.start_ns, e.duration_ns]
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(["host", "", e.name, e.start_ns, e.duration_ns]
                           for e in line.events if e.name in ANNOTATIONS)
    return out


def _union(intervals):
    """Merged, sorted (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
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


def _containers(ops) -> set:
    """Indices of ops that enclose another op of the same device (a
    ``while`` around its body's ops): the rest are leaves."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    found, stack = set(), []
    for i in order:
        s, e = ops[i][0], ops[i][0] + ops[i][1]
        while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][0] + ops[stack[-1]][1]:
            found.add(stack[-1])
        stack.append(i)
    return found


def _collective(op_name: str) -> bool:
    return any(c in instruction(op_name) for c in COLLECTIVES)


class Trace:
    """The events of one traced window, reduced per device."""

    def __init__(self, events: list):
        self.ops = defaultdict(list)        # device -> [(start, dur, name)]
        self.modules = defaultdict(list)
        self.async_ops = defaultdict(list)
        self.host = []                      # (start, end, name)
        for kind, device, name, start, dur in events:
            if kind == "op":
                self.ops[device].append((float(start), float(dur), name))
            elif kind == "module":
                self.modules[device].append((float(start), float(dur), name))
            elif kind == "async":
                self.async_ops[device].append((float(start), float(dur), name))
            elif kind == "host":
                self.host.append((float(start), float(start) + float(dur), name))
        for d in self.modules:
            self.modules[d].sort()
        windows = [(s, e) for s, e, n in self.host if n == "window"]
        if len(windows) != 1:
            raise ValueError(f"expected one 'window' span, found {len(windows)}")
        self.lo, self.hi = windows[0]
        self.devices = sorted(self.ops)
        if not self.devices:
            raise ValueError("no device operation in the trace")

    # -- time ---------------------------------------------------------------

    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _busy(self, device):
        return _union(_clip([(s, s + d) for s, d, _ in self.ops[device]],
                            self.lo, self.hi))

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return sum(_length(self._busy(d)) for d in self.devices) * 1e-9 / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # -- programs and kernels -----------------------------------------------

    def _module_at(self, device, t):
        mods = self.modules.get(device, [])
        i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
        if i >= 0 and mods[i][0] <= t <= mods[i][0] + mods[i][1]:
            return base(mods[i][2])
        return ""

    def module_runs(self, name: str | None = None):
        """(count, device seconds) of program executions inside the window
        whose name without its fingerprint is ``name`` (all programs when
        None), summed over devices."""
        n, t = 0, 0.0
        for d in self.devices:
            for s, dur, m in self.modules.get(d, []):
                if self.lo <= s < self.hi and (name is None or base(m) == name):
                    n += 1
                    t += dur
        return n, t * 1e-9

    def kernel(self, kernel: str, module: str | None = None):
        """(count, device seconds) of the executions of instructions named
        ``kernel`` (numeric suffix dropped) inside the window, optionally
        only those inside program ``module``; summed over devices."""
        n, t = 0, 0.0
        for d in self.devices:
            for s, dur, name in self.ops[d]:
                if not (self.lo <= s < self.hi):
                    continue
                if base(instruction(name)) != kernel:
                    continue
                if module is not None and self._module_at(d, s) != module:
                    continue
                n += 1
                t += dur
        return n, t * 1e-9

    def exposed_collective_s(self) -> float:
        """Seconds in which a collective (synchronous, or asynchronous in
        flight) ran on a device and no other leaf op did, averaged over
        the devices."""
        total = 0.0
        for d in self.devices:
            ops = self.ops[d]
            outer = _containers([(s, dur) for s, dur, _ in ops])
            coll, comp = [], []
            for i, (s, dur, name) in enumerate(ops):
                if i not in outer:
                    (coll if _collective(name) else comp).append((s, s + dur))
            coll += [(s, s + dur) for s, dur, name in self.async_ops.get(d, [])
                     if _collective(name)]
            total += _subtract(_union(_clip(coll, self.lo, self.hi)),
                               _union(_clip(comp, self.lo, self.hi)))
        return total * 1e-9 / len(self.devices)

    # -- breakdown ----------------------------------------------------------

    def top_ops(self, n: int = 10):
        """The leaf ops that took the most device time in the window, as
        [program/instruction, seconds per device]."""
        tot = defaultdict(float)
        for d in self.devices:
            ops = self.ops[d]
            outer = _containers([(s, dur) for s, dur, _ in ops])
            for i, (s, dur, name) in enumerate(ops):
                if i in outer or not (self.lo <= s < self.hi):
                    continue
                key = f"{self._module_at(d, s) or '?'}/{instruction(name)}"
                tot[key] += dur
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / len(self.devices)] for k, v in top]

    def idle_gaps(self, n: int = 10):
        """The longest gaps in the first device's busy time inside the
        window, each named after the innermost harness span around its
        middle (``none`` where only the window is)."""
        busy = self._busy(self.devices[0])
        gaps, cur = [], self.lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.hi:
            gaps.append((cur, self.hi))
        spans = [(s, e, nm) for s, e, nm in self.host if nm != "window"]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (s + e) / 2
            around = [(se - ss, nm) for ss, se, nm in spans if ss <= mid <= se]
            out.append([min(around)[1] if around else "none", (e - s) * 1e-9])
        return out
