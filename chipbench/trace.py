"""Reduction of a profiler trace to the device numbers the benchmark reports.

The JAX profiler writes an `.xplane.pb`. On a TPU it holds, per chip, a
plane `/device:TPU:<i>` whose line "XLA Modules" has one event per program
the chip ran and whose line "XLA Ops" has one event per operation (loop
operations such as `while` contain the operations of their body). The
host plane `/host:CPU` holds the job loop's own spans (the closed loop's
are `SPANS`), written with `jax.profiler.TraceAnnotation`, on the same
clock. Every loop opens a `dispatch` span around each call of a job.

  busy_s       union of the program intervals inside the window, averaged
               over the chips;
  window_s     from the start of the first job's `dispatch` span to the
               end of the last span;
  kernel_s     union of the Pallas kernels' intervals: until the program
               names its kernels, the operations whose HLO custom-call
               target is `tpu_custom_call`;
  device_ops   self time of each operation (its time less that of the
               operations it contains), the largest first;
  idle_gaps    the stretches of the window in which no program ran, the
               longest first, each named by the harness span that overlaps
               it most ("none" where no span does).
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

SPANS = ("dispatch", "block_wait", "to_host", "next_job")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds on the trace's clock."""

    modules: list[np.ndarray]   # per chip, (k, 2) program intervals
    ops: list[list[tuple[str, float, float]]]  # per chip, (name, start, end)
    spans: list[tuple[str, float, float]]      # harness spans


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    kernel_s: float
    device_ops: list
    idle_gaps: list


def find_xplane(log_dir: str) -> str:
    """The one `.xplane.pb` the profiler wrote under `log_dir`."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def load(path: str, span_names: tuple = SPANS) -> Trace:
    """Read the device programs and operations and the job loop's spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules, ops, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, chip_ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.end_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    chip_ops = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            modules.append(np.asarray(sorted(mods), np.float64).reshape(-1, 2))
            ops.append(chip_ops)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name in span_names]
    if not modules:
        raise RuntimeError(f"{path} holds no TPU device plane")
    return Trace(modules, ops, sorted(spans, key=lambda s: s[1]))


def union(intervals: np.ndarray) -> np.ndarray:
    """(k, 2) disjoint sorted intervals covering the same points."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Intervals cut to [lo, hi], empty ones dropped."""
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]] if len(iv) else iv


def covered(intervals: np.ndarray) -> float:
    """Total length of a union's intervals."""
    return float(np.sum(intervals[:, 1] - intervals[:, 0])) if len(intervals) else 0.0


def self_times(ops: list[tuple[str, float, float]]) -> dict[str, float]:
    """Time of each operation name less the time of the operations nested
    inside it (a loop's body), summed over its events."""
    order = sorted(ops, key=lambda o: (o[1], -(o[2] - o[1])))
    totals: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self time]

    def close(entry):
        totals[entry[0]] = totals.get(entry[0], 0.0) + entry[2]

    for name, start, end in order:
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    while stack:
        close(stack.pop())
    return totals


def op_label(hlo_text: str) -> str:
    """The operation's name, from the HLO text the trace names it by."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def summarize(tr: Trace, top: int = 10) -> Summary:
    """Reduce a loaded trace to the window's device numbers."""
    dispatch = [s for s in tr.spans if s[0] == "dispatch"]
    if not dispatch:
        raise RuntimeError("the trace holds no dispatch span")
    lo = dispatch[0][1]
    hi = max(s[2] for s in tr.spans)
    window = hi - lo
    busy, kernel, op_self = [], [], {}
    gaps = []
    for mods, ops in zip(tr.modules, tr.ops):
        prog = clip(union(mods), lo, hi)
        busy.append(covered(prog))
        kern = np.asarray([(a, b) for name, a, b in ops if KERNEL_MARK in name]).reshape(-1, 2)
        kernel.append(covered(clip(union(kern), lo, hi)))
        inside = [(op_label(n), a, b) for n, a, b in ops if a >= lo and b <= hi]
        for name, t in self_times(inside).items():
            op_self[name] = op_self.get(name, 0.0) + t
        edges = np.concatenate([[lo], prog.reshape(-1), [hi]]).reshape(-1, 2)
        gaps += [(a, b) for a, b in edges if b > a]
    chips = len(tr.modules)
    spans = np.asarray([(a, b) for _, a, b in tr.spans]).reshape(-1, 2)

    def name_gap(a, b):
        if not len(spans):
            return "none"
        overlap = np.minimum(spans[:, 1], b) - np.maximum(spans[:, 0], a)
        i = int(np.argmax(overlap))
        return tr.spans[i][0] if overlap[i] > 0 else "none"

    gaps.sort(key=lambda g: g[0] - g[1])
    return Summary(
        busy_s=sum(busy) / chips * 1e-9,
        window_s=window * 1e-9,
        kernel_s=sum(kernel) / chips * 1e-9,
        device_ops=[[k, v / chips * 1e-9] for k, v in
                    sorted(op_self.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[name_gap(a, b), float(b - a) * 1e-9] for a, b in gaps[:top]],
    )
