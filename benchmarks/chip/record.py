"""What a run leaves for the metric readers: requests, steps, work, trace."""
from __future__ import annotations

import bisect
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class RequestRecord:
    """One request as the client saw it, in seconds from the window start."""

    uid: int
    due: float
    max_new: int
    prompt_len: int = 0
    submitted: Optional[float] = None
    admitted: Optional[float] = None     # start of the step that admitted it
    first: Optional[float] = None        # host holds its first token
    last: Optional[float] = None         # host holds its last token
    n_tokens: int = 0
    n_chunks: int = 0                    # steps that delivered tokens to it
    done: bool = False
    failed: bool = False
    tokens: Optional[np.ndarray] = None  # the served tokens, once done


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (a missing value is +inf and counts)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    rank = max(int(math.ceil(p / 100.0 * v.size)), 1)
    return float(v[rank - 1])


def ttft_s(r: RequestRecord) -> float:
    """Due time to first token on the host; a failed request is missing."""
    if r.failed or r.first is None:
        return math.inf
    return r.first - r.due


def tpot_s(r: RequestRecord) -> Optional[float]:
    """(last - first) / (tokens - 1) for outputs that span two decode chunks
    or more; None for a one-chunk output, +inf for a failed request."""
    if r.failed:
        return math.inf
    if r.n_chunks < 2 or r.n_tokens < 2 or r.first is None:
        return None
    return (r.last - r.first) / (r.n_tokens - 1)


@dataclass
class RunRecord:
    kind: str                            # "serve" | "train"
    seconds: float                       # window length asked for
    window_s: float = 0.0                # window length measured
    setup_s: float = 0.0
    requests: List[RequestRecord] = field(default_factory=list)
    tokens_in_window: int = 0            # tokens the host held by the close
    steps: int = 0                       # train steps in the window
    tokens_per_step: int = 0
    work: Dict[str, float] = field(default_factory=dict)
    trace: Optional[Dict[str, Any]] = None
    traced_work: Optional[Dict[str, float]] = None   # work in the traced span
    traced_until: Optional[float] = None  # the traced span's end, s
    peaks: Dict[str, float] = field(default_factory=dict)
    counters: Dict[str, Any] = field(default_factory=dict)
    chips: int = 1
    step_times: List[Tuple[float, float]] = field(default_factory=list)

    def due_in_window(self) -> List[RequestRecord]:
        return [r for r in self.requests if r.due < self.seconds]

    def due_in_trace(self) -> List[RequestRecord]:
        """Requests due in the traced span (the whole window untraced)."""
        end = self.seconds if self.traced_until is None else min(
            self.seconds, self.traced_until)
        return [r for r in self.requests if r.due < end]

    def waited_for_slot(self, r: RequestRecord) -> bool:
        """Whether an engine step began after ``r`` was submitted and before
        the step that admitted it: a step that found no free slot for it."""
        if r.submitted is None or r.admitted is None:
            return False
        starts = [s for s, _ in self.step_times]
        i = bisect.bisect_right(starts, r.submitted)
        return i < len(starts) and starts[i] < r.admitted
