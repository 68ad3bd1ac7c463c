"""Reduces a profiler trace (``.xplane.pb``, read through
``jax.profiler.ProfileData``) to what the per-layer metrics need.

Device planes (``/device:TPU:<n>``) give two lines: ``XLA Ops``, one event
per operation run, and ``XLA Modules``, one event per program execution.
The host plane gives the benchmark's own spans (``bench.*``). All share
one clock. A program is found by the module name the bound program
reports at set-up, never by a name written here.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
SPAN_PREFIX = "bench."
# operations whose interval holds other operations of the same line
CONTAINERS = (["while"], ["call"], ["conditional"])


@dataclasses.dataclass
class Device:
    ops: np.ndarray                 # (n, 2) float seconds: start, end
    op_names: List[str]
    modules: List[Tuple[str, float, float]]   # (module name, start, end)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Device]
    spans: List[Tuple[str, float, float]]     # the benchmark's host spans

    @property
    def window(self) -> Tuple[float, float]:
        """From the first to the last host span of the traced loop."""
        return (min(s for _, s, _ in self.spans),
                max(e for _, _, e in self.spans))


def _module_base(name: str) -> str:
    """'jit_serve_step(12)' -> 'jit_serve_step'."""
    return re.sub(r"\(\d+\)$", "", name)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Device] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, names, modules = [], [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        ops.append((e.start_ns * 1e-9, e.end_ns * 1e-9))
                        names.append(e.name)
                elif line.name == MODULES_LINE:
                    modules.extend((_module_base(e.name), e.start_ns * 1e-9,
                                    e.end_ns * 1e-9) for e in line.events)
            if ops:
                arr = np.asarray(ops, np.float64).reshape(-1, 2)
                order = np.argsort(arr[:, 0], kind="stable")
                devices[plane.name] = Device(
                    arr[order], [names[i] for i in order], modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      e.end_ns * 1e-9))
    if not devices:
        raise ValueError(f"{path}: no device plane with '{OPS_LINE}'")
    spans.sort(key=lambda s: s[1])
    return Trace(devices, spans)


def busy_intervals(ops: np.ndarray, t0: float, t1: float) -> np.ndarray:
    """The union of op intervals clipped to [t0, t1], as disjoint
    (start, end) rows in order."""
    if len(ops) == 0:
        return np.zeros((0, 2))
    iv = np.clip(ops, t0, t1)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    # a new block starts where an op begins after every earlier one ended
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    block_end = np.maximum.reduceat(iv[:, 1], np.flatnonzero(new))
    return np.stack([starts, block_end], axis=1)


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, mean over the devices."""
    t0, t1 = trace.window
    return float(np.mean([
        np.sum(np.diff(busy_intervals(d.ops, t0, t1), axis=1))
        for d in trace.devices.values()]))


def idle_share(trace: Trace) -> float:
    t0, t1 = trace.window
    return 1.0 - busy_s(trace) / (t1 - t0)


def program_times(trace: Trace, module: str) -> List[float]:
    """Device seconds of each execution of ``module`` inside the window,
    on every device."""
    t0, t1 = trace.window
    return [e - s for d in trace.devices.values()
            for name, s, e in d.modules
            if name == module and s >= t0 and e <= t1]


def _op_label(text: str) -> str:
    """'%fusion.3 = f32[4] fusion(...), kind=kLoop, ...' -> 'fusion.3
    fusion kLoop': the instruction, its opcode and kind or target."""
    m = re.match(r"%?([\w.-]+)\s*=\s*.*?\s([a-z][\w-]*)\(", text)
    if m is None:
        return text[:80]
    extra = re.search(r"kind=(\w+)|custom_call_target=\"([\w-]+)\"", text)
    tail = (" " + (extra.group(1) or extra.group(2))) if extra else ""
    return f"{m.group(1)} {m.group(2)}{tail}"


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The operations that took the most device time inside the window,
    summed over devices, each named by the program it ran in."""
    t0, t1 = trace.window
    total: Dict[str, float] = {}
    for d in trace.devices.values():
        dur = np.clip(d.ops[:, 1], t0, t1) - np.clip(d.ops[:, 0], t0, t1)
        mods = sorted((s, e, name) for name, s, e in d.modules)
        starts = np.asarray([s for s, _, _ in mods])
        where = np.searchsorted(starts, d.ops[:, 0], side="right") - 1
        for k, (text, x) in enumerate(zip(d.op_names, dur)):
            label = _op_label(text)
            if x <= 0 or label.split(" ")[1:2] in CONTAINERS:
                continue        # a loop or a call: its body's ops count
            i = where[k]
            prog = mods[i][2] if i >= 0 and d.ops[k, 0] < mods[i][1] \
                else "?"
            label = f"{prog} {label}"
            total[label] = total.get(label, 0.0) + float(x)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """Device idle time inside the window, split by the host span each
    part of a gap fell in ("other" where no span covers it), mean over
    devices."""
    t0, t1 = trace.window
    spans = trace.spans
    starts = np.asarray([s for _, s, _ in spans])
    total: Dict[str, float] = {}
    for d in trace.devices.values():
        busy = busy_intervals(d.ops, t0, t1)
        edges = np.concatenate([[t0], busy.ravel(), [t1]]).reshape(-1, 2)
        for gs, ge in edges:
            if ge <= gs:
                continue
            covered = 0.0
            i = max(0, int(np.searchsorted(starts, gs, side="right")) - 1)
            while i < len(spans) and spans[i][1] < ge:
                name, s, e = spans[i]
                part = min(e, ge) - max(s, gs)
                if part > 0:
                    total[name] = total.get(name, 0.0) + part
                    covered += part
                i += 1
            if ge - gs - covered > 1e-9:
                total["other"] = total.get("other", 0.0) + ge - gs - covered
    k = len(trace.devices)
    return [[name, v / k] for name, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def module_of(program, *args):
    """The module name a bound program reports: a compiled executable's
    HLO module, or what a jitted function lowers to for ``args``; None for
    a plain function."""
    if hasattr(program, "runtime_executable"):
        return program.runtime_executable().hlo_modules()[0].name
    if not hasattr(program, "lower"):
        return None
    text = program.lower(*args).as_text()
    m = re.search(r"module @([\w.$-]+)", text)
    if m is None:
        raise ValueError("no module name in the lowered program")
    return m.group(1)
