"""What a metric reader gets: the run's host record, its reduced trace (in
a traced run), the configuration's architecture module and sizes and the
chip's peaks, with the arithmetic the readers share. The operations and
bytes a step needs are computed by the architecture module from the
configuration's shapes (``decode_work``, ``prefill_flops``), never taken
from the program; here they are summed over the traced steps.
"""
from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

from loop import Record, Step


@dataclasses.dataclass
class Run:
    rec: Record
    arch: ModuleType                    # bench/arch/<arch>.py
    dims: object                        # the sizes ``arch.sizes`` read
    deployment: dict
    chips: int
    peak: dict                          # the device's row of peaks.json
    setup_s: float
    memory_peak: List[int]              # peak bytes in use, per chip
    device_of: Dict[str, str]           # tenant -> hypervisor device
    modules: Dict[str, str]             # "decode"/"prefill" -> module name
    trace: object = None                # trace_reduce.Trace, traced runs

    @property
    def window_s(self) -> float:
        return self.rec.t1 - self.rec.t0

    # ---- host records ----
    def due_in_window(self):
        return [s for s in self.rec.sent if s.due < self.rec.t1]

    def gaps_ms(self) -> np.ndarray:
        """Gaps between consecutive output tokens of every request, in the
        window."""
        g = [np.diff(s.token_times) for s in self.rec.sent
             if len(s.token_times) > 1]
        return np.concatenate(g) * 1e3 if g else np.zeros(0)

    def traced_steps(self) -> List[Step]:
        """The fleet steps that ran wholly while the profiler was on."""
        t = self.rec.trace_t0
        return [] if t is None else [s for s in self.rec.steps
                                     if s.start >= t]

    # ---- work the traced steps needed ----
    def decode_work(self, steps: List[Step]):
        """(executions, bytes, flops) of the decode steps in ``steps``: one
        execution per device that decoded, over the rows it decoded, each
        counted by the architecture's ``decode_work``."""
        execs, nbytes, flops = 0, 0, 0.0
        for st in steps:
            rows: Dict[str, List[int]] = {}
            for i, j in st.tokens:
                s = self.rec.sent[i]
                rows.setdefault(self.device_of[s.tenant], []).append(
                    len(s.arrival.prompt) + j)
            for ctxs in rows.values():
                b, f = self.arch.decode_work(self.dims, ctxs)
                execs += 1
                nbytes += b
                flops += f
        return execs, nbytes, flops

    def prefill_work(self, steps: List[Step]):
        """(real context tokens, flops) of the prefills in ``steps``: a
        request's first token comes from the step that prefilled its
        prompt but the last token, which that step decodes."""
        toks, flops = 0, 0.0
        for st in steps:
            for i, j in st.tokens:
                if j == 0:
                    S = len(self.rec.sent[i].arrival.prompt) - 1
                    toks += S
                    flops += self.arch.prefill_flops(self.dims, S)
        return toks, flops


def percentile(x, q: float) -> Optional[float]:
    x = np.asarray(x, np.float64)
    return float(np.percentile(x, q)) if x.size else None
