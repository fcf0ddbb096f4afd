"""From a profiler trace to device busy time, time per layer and the
longest idle gaps.

A traced run writes one ``.xplane.pb``.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
HLO instruction, named by the instruction's text (``%fusion.12 = ...``),
on the same clock as the host planes.  An instruction that contains
others (a ``while`` around a kernel call) has its children as events of
the same line inside its interval; each event's *self* time is the part
of its interval inside the window less its children's parts there (an
event that the window's end cuts off may have lost its children).

Busy time is the union of the op intervals inside the window, averaged
over the devices; idle is the rest of the window.  Each idle gap is named
by the benchmark's host span (``jax.profiler.TraceAnnotation``, prefix
``bench/``) that overlaps it most.

Layers come from the compiled HLO (:func:`parse_hlo`): each instruction
carries the Python call stack that made it, and a system's layer rules
(:func:`load_layers`, one data file per rule under
``bench/layers/<system>/``) map what an instruction is and where it was
made to a layer.  A layout copy that no rule names takes the layer of
the instruction that consumes it.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import re

WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"

MATCH_KEYS = ("target", "opcode", "stack", "op_name")


def load_layers(directory) -> tuple:
    """A system's layer rules: every ``*.json`` file of ``directory`` in
    name order, each ``{"layer": name, "match": {key: [values]}}``.  The
    first rule that matches an instruction names its layer; a rule
    matches where every key it gives does: ``target`` the custom-call
    target, ``opcode`` the HLO opcode, ``stack`` any of these function
    names on the instruction's call stack, ``op_name`` any of these
    strings in its ``op_name``.  A new layer is a new file, its place in
    the order given by its name."""
    from pathlib import Path
    rules = []
    for f in sorted(Path(directory).glob("*.json")):
        r = json.loads(f.read_text())
        bad = set(r["match"]) - set(MATCH_KEYS)
        if bad or not r["match"]:
            raise ValueError(f"{f}: match keys {sorted(r['match'])}")
        rules.append((r["layer"], {k: tuple(v) for k, v in r["match"].items()}))
    if not rules:
        raise ValueError(f"no layer rules in {directory}")
    return tuple(rules)


def _matches(ins: "Instr", rule: dict) -> bool:
    tests = {"target": lambda v: ins.target in v,
             "opcode": lambda v: ins.opcode in v,
             "stack": lambda v: bool(set(ins.stack) & set(v)),
             "op_name": lambda v: any(x in ins.op_name for x in v)}
    return all(tests[k](v) for k, v in rule.items())


# ------------------------------------------------------------------ HLO --

@dataclasses.dataclass
class Instr:
    opcode: str
    target: str
    stack: tuple
    operands: tuple
    op_name: str = ""
    comp: str = ""         # the computation it belongs to
    body: str = ""         # a while's body computation


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{$")
_TABLE = re.compile(r"^(\d+) (.*)$")


def _skip_shape(s: str) -> int:
    """Index just past the result shape at the start of ``s``."""
    if not s.startswith("("):
        return s.index(" ")
    depth = 0
    for i, c in enumerate(s):
        depth += c == "("
        depth -= c == ")"
        if depth == 0:
            return i + 1
    return len(s)


def parse_hlo(text: str) -> dict[str, Instr]:
    """Every instruction of a compiled module's text: opcode, custom-call
    target, the function names on its call stack (innermost first), and
    its operands."""
    tables: dict[str, dict[int, str]] = {}
    section = None
    comp = ""
    instrs: dict[str, Instr] = {}
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = line
            tables[section] = {}
            continue
        m = _TABLE.match(line)
        if section and m:
            tables[section][int(m.group(1))] = m.group(2)
            continue
        section = None
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        j = _skip_shape(rest)
        k = rest.index("(", j)
        opcode = rest[j:k].strip()
        depth, end = 0, len(rest)
        for i in range(k, len(rest)):
            depth += rest[i] == "("
            depth -= rest[i] == ")"
            if depth == 0:
                end = i
                break
        operands = tuple(re.findall(r"%([\w.\-]+)", rest[k:end]))
        t = re.search(r'custom_call_target="([^"]*)"', rest)
        f = re.search(r"stack_frame_id=(\d+)", rest)
        o = re.search(r'op_name="([^"]*)"', rest)
        b = re.search(r"body=%([\w.\-]+)", rest)
        instrs[name] = Instr(opcode, t.group(1) if t else "",
                             _stack(tables, int(f.group(1))) if f else (),
                             operands, o.group(1) if o else "", comp,
                             b.group(1) if b else "")
    return instrs


def _stack(tables: dict, frame: int) -> tuple:
    funcs = tables.get("FunctionNames", {})
    locs = tables.get("FileLocations", {})
    frames = tables.get("StackFrames", {})
    out = []
    seen = set()
    while frame in frames and frame not in seen:
        seen.add(frame)
        fr = dict(re.findall(r"(\w+)=(\d+)", frames[frame]))
        loc = dict(re.findall(r"(\w+)=(\d+)", locs.get(int(fr["file_location_id"]), "")))
        fn = funcs.get(int(loc.get("function_name_id", 0)), "")
        out.append(fn.strip('"'))
        frame = int(fr["parent_frame_id"]) - 1     # printed one-based
    return tuple(out)


def classify(instrs: dict[str, Instr], layers: tuple) -> dict[str, str]:
    """Instruction name -> layer by ``layers`` (:func:`load_layers`;
    ``other`` where no rule matches)."""
    out: dict[str, str] = {}
    for name, ins in instrs.items():
        for layer, rule in layers:
            if _matches(ins, rule):
                out[name] = layer
                break
    # a while without a stack of its own: the layer most of its body has
    in_comp: dict[str, list] = {}
    for name, ins in instrs.items():
        if name in out:
            in_comp.setdefault(ins.comp, []).append(out[name])
    for name, ins in instrs.items():
        if name not in out and ins.opcode == "while" and in_comp.get(ins.body):
            got = in_comp[ins.body]
            out[name] = max(set(got), key=got.count)
    # and the rest of a classified while's body goes with it
    body_layer = {ins.body: out[n] for n, ins in instrs.items()
                  if ins.opcode == "while" and n in out}
    for name, ins in instrs.items():
        if name not in out and ins.comp in body_layer:
            out[name] = body_layer[ins.comp]
    # a layout copy: the layer of what consumes it
    users: dict[str, list] = {}
    for name, ins in instrs.items():
        for op in ins.operands:
            users.setdefault(op, []).append(name)
    moves = ("copy", "copy-start", "copy-done", "transpose", "bitcast",
             "tuple")
    for _ in range(4):
        for name, ins in instrs.items():
            if name not in out and ins.opcode in moves:
                named = [out[u] for u in users.get(name, ()) if u in out]
                if named:
                    out[name] = named[0]
    return {n: out.get(n, "other") for n in instrs}


# ---------------------------------------------------------------- trace --

def start(trace_dir: str) -> None:
    """Start the profiler with its Python-call tracer off (it would slow
    the host path under test); the ``TraceAnnotation`` spans stay on."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


@dataclasses.dataclass
class Op:
    name: str          # the instruction name (no leading %)
    label: str         # the instruction text, cut short
    start: float       # ns
    end: float
    self_ns: float = 0.0


def _instr_name(event_name: str) -> str:
    m = re.match(r"^%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


def _self_times(ops: list[Op], window: tuple[float, float]) -> None:
    w0, w1 = window

    def inside(o: Op) -> float:
        return max(0.0, min(o.end, w1) - max(o.start, w0))

    ops.sort(key=lambda o: (o.start, -o.end))
    stack: list[Op] = []
    for o in ops:
        o.self_ns = inside(o)
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= inside(o)
        stack.append(o)


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Trace:
    """Device ops per device, host spans, and the window, in ns."""

    def __init__(self, devices: list[list[Op]], spans: list[tuple],
                 window: tuple[float, float]):
        self.devices = devices
        self.spans = spans
        self.window = window
        for ops in devices:
            _self_times(ops, window)

    # --------------------------------------------------------------- io --
    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        devices, spans = [], []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                ops = []
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for e in line.events:
                        ops.append(Op(_instr_name(e.name), e.name[:80],
                                      e.start_ns, e.start_ns + e.duration_ns))
                devices.append(ops)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append((e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
        win = [s for s in spans if s[0] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"no {WINDOW_SPAN} span in {path}")
        return cls(devices, [s for s in spans if s[0] != WINDOW_SPAN],
                   (win[0][1], win[0][2]))

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
        if not paths:
            raise ValueError(f"no trace under {trace_dir}")
        return cls.from_xplane(paths[-1])

    def to_json(self) -> str:
        return json.dumps({
            "window": self.window, "spans": self.spans,
            "devices": [[[o.name, o.label, o.start, o.end] for o in ops]
                        for ops in self.devices]})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls([[Op(*o) for o in ops] for ops in d["devices"]],
                   [tuple(s) for s in d["spans"]], tuple(d["window"]))

    # ---------------------------------------------------------- numbers --
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, ops) -> list[tuple[float, float]]:
        w0, w1 = self.window
        return _union((max(o.start, w0), min(o.end, w1)) for o in ops
                      if o.end > w0 and o.start < w1)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(b - a for ops in self.devices for a, b in self._busy(ops))
        return tot / len(self.devices) * 1e-9

    def layer_seconds(self, layer_of: dict[str, str]) -> dict[str, float]:
        """Self time per layer inside the window, averaged over devices;
        ops of instructions that ``layer_of`` does not know are ``other``."""
        out: dict[str, float] = {}
        for ops in self.devices:
            for o in ops:
                if not o.self_ns:
                    continue
                k = layer_of.get(o.name, "other")
                out[k] = out.get(k, 0.0) + o.self_ns * 1e-9 / len(self.devices)
        return out

    def top_ops(self, k: int = 10, layer_of: dict | None = None) -> list:
        """The ``k`` instructions with the most self time in the window:
        ``[label, seconds]``, averaged over devices."""
        tot: dict[str, float] = {}
        label: dict[str, str] = {}
        for ops in self.devices:
            for o in ops:
                if not o.self_ns:
                    continue
                tot[o.name] = tot.get(o.name, 0.0) + o.self_ns * 1e-9 / len(self.devices)
                label[o.name] = o.label.split("{")[0].lstrip("%")
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[(f"{layer_of.get(n, 'other')}: " if layer_of else "")
                 + label[n], s] for n, s in rows]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest idle gaps of the first device inside the
        window: ``[what the host was doing, seconds]``."""
        if not self.devices:
            return []
        busy = self._busy(self.devices[0])
        w0, w1 = self.window
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            best, name = 0.0, "no bench span"
            for s, s0, s1 in self.spans:
                ov = min(b, s1) - max(a, s0)
                if ov > best:
                    best, name = ov, s
            out.append([name, (b - a) * 1e-9])
        return out
