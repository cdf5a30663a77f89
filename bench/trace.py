"""One reduction from a profiler trace (``.xplane.pb``) to device metrics.

* busy time: the union of the intervals in which an operation ran on a
  device, per device; the benchmark reports its mean over devices;
* idle share: 1 - busy / window;
* per-kernel device time and event count, found by a predicate over the
  event's lower-cased text (its name, HLO op and module names).  On the
  TPU a Pallas kernel is an operation whose text holds
  ``custom_call_target="tpu_custom_call"`` and its operand shapes;
* the longest idle gaps of the first device, each labelled with the
  benchmark span (a host ``TraceAnnotation``) it fell in.

Device planes are ``/device:TPU:<n>`` and their operations lie on the
``XLA Ops`` line.  A trace recorded on the CPU keeps its operations on the
host plane instead; ``summarize`` takes the plane and line filters so the
same code reads both.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from pathlib import Path
from typing import Callable, Dict, List, Sequence, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip, by JAX's ``device_kind``.  A device the
    table lacks is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def xla_ops_line(name: str) -> bool:
    return name == "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str
    start: float          # ns
    end: float            # ns
    text: str             # lower-case name + HLO op / module stats


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: List[float]                       # per device
    ops: List[List[Op]]                       # per device, sorted by start
    spans: List[Tuple[str, float, float]]     # host spans (name, start, end)

    @property
    def n_devices(self) -> int:
        return len(self.busy_s)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s

    def kernel_seconds(self, match: Callable[[str], bool]) -> float:
        """Summed device time of the events whose text ``match`` accepts,
        over all devices."""
        return sum(o.end - o.start for ops in self.ops for o in ops
                   if match(o.text)) * 1e-9

    def kernel_count(self, match: Callable[[str], bool]) -> int:
        return sum(1 for ops in self.ops for o in ops if match(o.text))

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` kinds of operation (opcode and result shape) that took
        most device time, seconds summed over devices and divided by the
        device count."""
        tot: Dict[str, float] = {}
        for ops in self.ops:
            for o in ops:
                key = op_kind(o.name)
                tot[key] = tot.get(key, 0.0) + (o.end - o.start)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [(n, t * 1e-9 / self.n_devices) for n, t in top]

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` longest idle gaps of the first device inside the
        window, each named by the host span around its midpoint."""
        merged = _union(self.ops[0])
        gaps = [(a_end, b_start) for (_, a_end), (b_start, _) in
                zip(merged, merged[1:]) if b_start > a_end]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for lo, hi in gaps[:k]:
            mid = 0.5 * (lo + hi)
            label = "outside bench spans"
            for name, s, e in self.spans:
                if s <= mid <= e:
                    label = name          # innermost: spans are start-sorted
            out.append((label, (hi - lo) * 1e-9))
        return out


_HLO_OP = re.compile(r"%?\S+ = (\([^()]*\)|\S+) ([\w.-]+)\(")


def op_kind(name: str) -> str:
    """``%run.7 = f32[4096,896]{1,0:T(8,128)} custom-call(...)`` ->
    ``custom-call f32[4096,896]`` (tuple results keep their parentheses);
    other names unchanged."""
    m = _HLO_OP.match(re.sub(r"{[^}]*}", "", name))
    return f"{m.group(2)} {m.group(1)}" if m else name


def _union(ops: Sequence[Op]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for o in sorted(ops, key=lambda o: o.start):
        if out and o.start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], o.end))
        else:
            out.append((o.start, o.end))
    return out


def _stat_text(ev) -> str:
    parts = [ev.name]
    for key, val in ev.stats:
        if key in ("hlo_op", "hlo_module", "long_name", "tf_op", "name"):
            parts.append(str(val))
    return " ".join(parts).lower()


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(found)}")
    return found[0]


def summarize(path: str, window: Tuple[float, float] | None = None, *,
              plane_filter: Callable[[str], bool] = tpu_plane,
              line_filter: Callable[[str], bool] = xla_ops_line,
              span_prefix: str = "bench.") -> TraceSummary:
    """Reduce one ``.xplane.pb``.  ``window`` (start, end) in the trace's
    ns clock bounds the measured window; by default it is the extent of the
    host spans named ``span_prefix*``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops_by_dev: List[List[Op]] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        if not plane_filter(plane.name):
            continue
        ops: List[Op] = []
        for line in plane.lines:
            if not line_filter(line.name):
                continue
            for ev in line.events:
                if ev.duration_ns > 0 and not ev.name.startswith("end: "):
                    ops.append(Op(ev.name, ev.start_ns, ev.end_ns,
                                  _stat_text(ev)))
        ops_by_dev.append(sorted(ops, key=lambda o: o.start))
    spans.sort(key=lambda s: s[1])
    if not ops_by_dev or not any(ops_by_dev):
        raise ValueError(f"no device operations in {path}")
    if window is None:
        if not spans:
            raise ValueError(f"no '{span_prefix}' spans in {path}")
        window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
    lo, hi = window
    clipped = [[Op(o.name, max(o.start, lo), min(o.end, hi), o.text)
                for o in ops if o.end > lo and o.start < hi]
               for ops in ops_by_dev]
    busy = [sum(e - s for s, e in _union(ops)) * 1e-9 for ops in clipped]
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy, ops=clipped,
                        spans=[s for s in spans if s[2] > lo and s[1] < hi])
