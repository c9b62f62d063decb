"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device ops with their HLO text, and the harness's host spans.

* Device ops are the events of each device plane's op line, less the
  control flow (a while loop's event spans the ops of its body). Busy
  time is the union of their intervals, never the sum: ops that overlap
  on one device count once.
* Host spans are the harness's own ``TraceAnnotation`` events
  (``make_response``, ``fit``, ``fetch_result``), on the same clock.
* The traced window runs from the first span's start to the last span's
  end; busy and idle are measured inside it.
* An op reads the design A when one of its operands has A's shape, a
  node's (m, n) block, or either transposed; over several chips also one
  chip's shard (N / chips, m, n) or its transpose. The reduction reads
  this from the op's HLO text and never from a kernel's name, so a Mosaic
  custom call and an XLA fusion over the same A read the same bytes.
* An op is a collective by its HLO opcode (``COLLECTIVES``), never by
  its name.
"""
from __future__ import annotations

import dataclasses
import functools
import re

SPANS = ("make_response", "fit", "fetch_result")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OP_LINES = ("XLA Ops",)
# control flow whose event spans the ops of its body
CONTAINERS = ("while", "conditional", "call")
# an operand's type: "f32[4,75000,4000]{2,1,0:T(8,128)}" -> dtype, dims,
# layout
SHAPE = re.compile(r"^(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                   r"\[([\d,]*)\](\{[^}]*\})?")
# a layout's memory space: S(1) and up are on-chip memories, not HBM
ON_CHIP = re.compile(r"S\(([1-9]\d*)\)")
# exchanges between chips, by opcode, with their asynchronous halves
COLLECTIVES = frozenset(
    op + part
    for op in ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
    for part in ("", "-start", "-done"))
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    start_ns: float
    dur_ns: float
    text: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    ops: list[Op]
    spans: list[Span]

    @property
    def devices(self) -> list[int]:
        return sorted({o.device for o in self.ops})

    @property
    def window(self) -> tuple[float, float]:
        return (min(s.start_ns for s in self.spans),
                max(s.end_ns for s in self.spans))

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def device_ops(self, device: int) -> list[Op]:
        lo, hi = self.window
        return [o for o in self.ops if o.device == device
                and o.end_ns > lo and o.start_ns < hi]

    def busy_intervals(self, device: int) -> list[tuple[float, float]]:
        """Union of the device's op intervals, clipped to the window."""
        lo, hi = self.window
        return union([(max(o.start_ns, lo), min(o.end_ns, hi))
                      for o in self.device_ops(device)])

    def busy_s(self, device: int) -> float:
        return sum(b - a for a, b in self.busy_intervals(device)) * 1e-9

    def mean_busy_s(self) -> float:
        devs = self.devices
        return sum(self.busy_s(d) for d in devs) / len(devs) if devs else 0.0

    def idle_gaps(self, device: int) -> list[tuple[str, float]]:
        """(label, seconds) of every gap between busy intervals inside the
        window, labelled with the innermost harness span open at its
        midpoint ("none" where no span is open)."""
        lo, hi = self.window
        gaps, t = [], lo
        for a, b in self.busy_intervals(device) + [(hi, hi)]:
            if a > t:
                gaps.append((self.span_at((t + a) / 2), (a - t) * 1e-9))
            t = max(t, b)
        return gaps

    def span_at(self, t: float) -> str:
        open_ = [s for s in self.spans if s.start_ns <= t < s.end_ns]
        if not open_:
            return "none"
        return min(open_, key=lambda s: s.end_ns - s.start_ns).name


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _close(text: str, i: int) -> int:
    """Index just past the bracket that closes the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in "([{":
            depth += 1
        elif text[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _split_top(text: str) -> list[str]:
    """``text`` split at the commas outside any brackets."""
    parts, depth, start = [], 0, 0
    for j, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(text[start:j].strip())
            start = j + 1
    parts.append(text[start:].strip())
    return [p for p in parts if p]


def _operand_list(text: str) -> str | None:
    """The text inside the opcode's parentheses of an HLO instruction
    ``%name = <type> opcode(<operands>), attributes``."""
    eq = text.find(" = ")
    if eq < 0:
        return None
    i = eq + 3
    i = _close(text, i) if text[i:i + 1] == "(" else text.find(" ", i)
    if i < 0:
        return None
    paren = text.find("(", i)
    if paren < 0:
        return None
    return text[paren + 1:_close(text, paren) - 1]


def operand_shapes(text: str, *, hbm_only: bool = False
                   ) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of each array operand of an HLO instruction; its own
    result, before the opcode, and tuple-typed operands (such as the
    buffers an async copy-done waits on) are left out. ``hbm_only`` also
    leaves out operands whose layout puts them in on-chip memory."""
    ops = _operand_list(text)
    if ops is None:
        return []
    out = []
    for operand in _split_top(ops):
        m = SHAPE.match(operand)
        if m and not (hbm_only and ON_CHIP.search(m.group(3) or "")):
            out.append((m.group(1),
                        tuple(int(d) for d in m.group(2).split(",") if d)))
    return out


@functools.lru_cache(maxsize=None)
def opcode(text: str) -> str:
    """The opcode of an HLO instruction's text ("" where there is none)."""
    eq = text.find(" = ")
    if eq < 0:
        return ""
    rest = text[eq + 3:]
    i = _close(rest, 0) if rest.startswith("(") else rest.find(" ")
    return rest[i:].strip().split("(", 1)[0] if i > 0 else ""


def op_label(text: str) -> str:
    """A short name for an op: its instruction name, opcode and result
    dims, without layouts or operands."""
    eq = text.find(" = ")
    if eq < 0:
        return text[:120]
    rest = text[eq + 3:]
    i = _close(rest, 0) if rest.startswith("(") else rest.find(" ")
    result = re.sub(r"\{[^}]*\}|/\*[^*]*\*/", "", rest[:max(i, 0)])
    return f"{text[:eq]} {opcode(text)} {result[:60]}".strip()


def is_collective(op: Op) -> bool:
    return opcode(op.text) in COLLECTIVES


def a_shapes(design_shape, chips: int = 1) -> set[tuple[int, ...]]:
    """The operand shapes that hold all of A or one node's block of it,
    in either order of the two matrix dimensions; over several chips also
    one chip's shard of the nodes."""
    N, m, n = design_shape
    shapes = {(N, m, n), (N, n, m), (m, n), (n, m), (N * m, n), (n, N * m)}
    if chips > 1:
        shapes |= {(N // chips, m, n), (N // chips, n, m)}
    return shapes


def a_bytes(op: Op, design_shape, chips: int = 1) -> int:
    """Bytes of A that ``op`` reads from HBM: the size of each of its
    A-shaped operands (0 where it has none). An A-shaped operand that the
    compiler placed in on-chip memory (a small A, prefetched whole) is
    not read from HBM and is not counted."""
    return _a_bytes(op.text, tuple(design_shape), chips)


@functools.lru_cache(maxsize=None)
def _a_bytes(text: str, design_shape: tuple[int, int, int],
             chips: int) -> int:
    want = a_shapes(design_shape, chips)
    total = 0
    for dt, dims in operand_shapes(text, hbm_only=True):
        if dims in want:
            size = DTYPE_BYTES[dt]
            for d in dims:
                size *= d
            total += size
    return total


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        dm = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dm and line.name in OP_LINES:
                dev = int(dm.group(2))
                for e in line.events:
                    # a TPU op event is named by its HLO instruction text;
                    # a loop's own event spans its body's ops: left out
                    if opcode(e.name) not in CONTAINERS:
                        ops.append(Op(dev, e.start_ns, e.duration_ns,
                                      e.name))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name in SPANS:
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return Trace(ops, spans)

