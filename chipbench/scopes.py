"""Program spans and model scopes, read from a JAX profiler trace.

``chipbench.trace`` reads a trace from outside the program: programs by
name, kernels by instruction, and the harness's own spans.  The program
marks two things of its own, and this module reads them:

* program spans: ``repro.obs.spans.span`` enters a
  ``jax.profiler.TraceAnnotation``, so each span (``serve.generate``,
  ``serve.sample``, ...) is a host event on the device ops' clock;
* model scopes: ``jax.named_scope`` in ``repro.models`` (``attention``,
  ``layer_scan``, ...) lands in the ``op_name`` metadata of each HLO
  instruction.  The trace holds every compiled program's HLO in its
  ``/host:metadata`` plane, keyed by program id (the number in a
  program's name, ``jit_serve_step(<id>)``); ``load`` gives each device
  op the ``op_name`` of its instruction there.

``load`` returns what ``chipbench.trace.load`` returns, plus the program
spans among the ``host`` events and a sixth field on every ``op`` event,
its ``op_name`` ("" where the instruction has none).  :class:`ScopedTrace`
reduces them and takes five-field events too (no op then has a scope).
"""

from __future__ import annotations

import bisect
import functools
import glob
import re
from collections import defaultdict

from chipbench import trace as tr

#: Host spans of the program (``repro.obs`` spans), by name prefix.
PROGRAM_SPANS = ("serve.", "train.")
#: The model scopes of ``repro.models`` and ``repro.train``.
SCOPES = ("weight_cast", "embed", "layer_scan", "norm", "attention", "ffn",
          "moe", "ssm", "readout", "loss", "optimizer")

_WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


@functools.cache
def scope_path(op_name: str) -> tuple:
    """The model scopes in an instruction's ``op_name``, outermost first.
    Transformation wrappers are stripped, so a backward op reads as its
    forward: ``jit(train_step)/transpose(jvp(layer_scan))/while/body/
    closed_call/attention/dot_general`` -> ``("layer_scan", "attention")``.
    Where XLA merged several names (``a/mul;b/add``) the first counts.
    Cached: a trace repeats each instruction's ``op_name`` once a step."""
    out = []
    for part in op_name.split(";")[0].split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            out.append(part)
    return tuple(out)


# -- the HLO in the trace's metadata plane -------------------------------------

def _varint(b, i):
    v = shift = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << shift
        if c < 0x80:
            return v, i
        shift += 7


def _fields(b):
    """(field number, value) of one serialized protobuf message: an int for
    a varint, a memoryview for a length-delimited field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in the trace")
        yield key >> 3, v


def _one(b, field, default=b""):
    for f, v in _fields(b):
        if f == field:
            return v
    return default


def _instruction_op_names(hlo_proto) -> dict:
    """{instruction name: op_name} of a serialized ``HloProto``
    (hlo_module 1 > computations 3 > instructions 2 > name 1, metadata 7 >
    op_name 2)."""
    out = {}
    for comp in (v for f, v in _fields(_one(hlo_proto, 1)) if f == 3):
        for instr in (v for f, v in _fields(comp) if f == 2):
            name = op_name = b""
            for f, v in _fields(instr):
                if f == 1:
                    name = v
                elif f == 7:
                    op_name = _one(v, 2)
            out[bytes(name).decode()] = bytes(op_name).decode()
    return out


def op_names(xplane_path: str) -> dict:
    """{program id: {instruction name: op_name}} of every program whose HLO
    the trace holds (XSpace planes 1; the ``/host:metadata`` XPlane's
    event metadata 4 are the programs, their stat ``Hlo Proto`` the HLO)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in (v for f, v in _fields(space) if f == 1):
        if bytes(_one(plane, 2)) != b"/host:metadata":
            continue
        programs, stat_ids = [], set()
        for f, v in _fields(plane):
            if f == 4:                              # map<int64, XEventMetadata>
                programs.append(_one(v, 2))
            elif f == 5:                            # map<int64, XStatMetadata>
                meta = _one(v, 2)
                if bytes(_one(meta, 2)) == b"Hlo Proto":
                    stat_ids.add(_one(meta, 1, 0))
        for meta in programs:
            pid = _one(meta, 1, 0)
            for stat in (v for f, v in _fields(meta) if f == 5):
                fields = dict(_fields(stat))
                if fields.get(1) in stat_ids and 6 in fields:
                    out[pid] = _instruction_op_names(fields[6])
    return out


def program_id(module_name: str) -> int | None:
    """``jit_serve_step(1077...)`` -> 1077..."""
    m = _PROGRAM_ID.search(module_name)
    return int(m.group(1)) if m else None


# -- flat events ---------------------------------------------------------------

def load(log_dir: str) -> list:
    """Flat events of the one ``.xplane.pb`` under ``log_dir``: those of
    ``chipbench.trace.load``, the program spans, and each op's
    ``op_name`` as a sixth field."""
    import jax

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    names = op_names(paths[0])
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device = plane.name[len("/device:"):]
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, program_id(e.name))
                          for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            for line_name, kind in (("XLA Ops", "op"), ("Async XLA Ops", "async"),
                                    ("XLA Modules", "module")):
                for e in lines.get(line_name, []):
                    ev = [kind, device, e.name, e.start_ns, e.duration_ns]
                    if kind == "op":
                        i = bisect.bisect_right(starts, e.start_ns) - 1
                        pid = mods[i][2] if i >= 0 and e.start_ns <= mods[i][1] else None
                        ev.append(names.get(pid, {}).get(tr.instruction(e.name), ""))
                    out.append(ev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(["host", "", e.name, e.start_ns, e.duration_ns]
                           for e in line.events
                           if e.name in tr.ANNOTATIONS or e.name.startswith(PROGRAM_SPANS))
    return out


# -- the reduction -------------------------------------------------------------

def _parents(ops) -> list:
    """For each (start, duration) op of one device, the index of the
    innermost other op whose interval holds it (a ``while`` around its
    body's ops), or -1.  An op of no duration (the runtime's markers,
    ``custom-call``s of 0 ns) neither holds nor is held: one that starts
    with a fusion would otherwise make the fusion a container."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    parent, stack = [-1] * len(ops), []
    for i in order:
        if ops[i][1] <= 0:
            continue
        s, e = ops[i][0], ops[i][0] + ops[i][1]
        while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][0] + ops[stack[-1]][1]:
            parent[i] = stack[-1]
        stack.append(i)
    return parent


class ScopedTrace(tr.Trace):
    """:class:`chipbench.trace.Trace` with the program's spans and each
    op's model scope.  Every reduction of the base class gives what it
    gives on the same events; ``idle_gaps`` names a gap after a program
    span where one is the innermost span around it."""

    def __init__(self, events: list):
        super().__init__([e[:5] for e in events])
        self.op_names = defaultdict(list)   # device -> op_name, as self.ops
        for e in events:
            if e[0] == "op":
                self.op_names[e[1]].append(e[5] if len(e) > 5 else "")
        self._scoped = {}                   # module -> scope_seconds(module)

    def program_spans(self, name: str | None = None) -> list:
        """(start, end, name) of the program's spans, in order of start;
        only those named ``name`` when given."""
        return sorted(h for h in self.host
                      if h[2].startswith(PROGRAM_SPANS) and name in (None, h[2]))

    def scope_seconds(self, module: str) -> tuple:
        """(executions, {innermost scope: device seconds}) of the
        executions of program ``module`` that start inside the window, over
        all their leaf ops (one that runs past the window's end included,
        one that started before it left out, as ``module_runs`` counts
        them), summed over the devices.  An op with no model scope of its
        own (a copy the compiler put in) takes that of the op it runs
        inside (the layer scan's ``while``); ops under no scope count
        under ``""``, ops of no duration nowhere.  Reduced once per
        program."""
        if module not in self._scoped:
            self._scoped[module] = self._scope_seconds(module)
        runs, seconds = self._scoped[module]
        return runs, dict(seconds)

    def _scope_seconds(self, module: str) -> tuple:
        runs, _ = self.module_runs(module)
        out = defaultdict(float)
        for d in self.devices:
            mods = self.modules.get(d, [])
            starts = [m[0] for m in mods]
            counted = [self.lo <= s < self.hi and tr.base(name) == module
                       for s, _, name in mods]
            ops = self.ops[d]
            parent = _parents([(s, dur) for s, dur, _ in ops])
            outer = set(parent) - {-1}
            for i, (s, dur, _) in enumerate(ops):
                k = bisect.bisect_right(starts, s) - 1
                if i in outer or dur <= 0 or k < 0 or not counted[k] \
                        or s > mods[k][0] + mods[k][1]:
                    continue
                j, path = i, ()
                while j >= 0 and not (path := scope_path(self.op_names[d][j])):
                    j = parent[j]
                out[path[-1] if path else ""] += dur * 1e-9
        return runs, dict(out)

    def idle_s(self, span: str) -> float:
        """Seconds of the window in which the device was idle inside a
        program span named ``span``, averaged over the devices."""
        inside = tr._union(tr._clip([(s, e) for s, e, _ in self.program_spans(span)],
                                    self.lo, self.hi))
        return sum(tr._subtract(inside, self._busy(d))
                   for d in self.devices) * 1e-9 / len(self.devices)


def scope_ms_per_run(trace, module: str, scope: str) -> float | None:
    """Device milliseconds per execution of ``module`` in ops whose
    innermost model scope is ``scope``; None where the trace has no
    scopes (a trace read without them, or a program without them) or no
    execution of ``module``."""
    if not isinstance(trace, ScopedTrace):
        return None
    runs, seconds = trace.scope_seconds(module)
    if not runs or set(seconds) <= {""}:
        return None
    return 1e3 * seconds.get(scope, 0.0) / runs
