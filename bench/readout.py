"""What the metric readers share: the window's steps, requests and gaps,
read from the open loop's ``harness.Log`` (host clock, seconds)."""
from __future__ import annotations

import numpy as np


def window_steps(log) -> list:
    """Steps that started in the window (in a traced run, in its part
    before the trace, which ends at ``end_s``)."""
    return [s for s in log.steps if log.open_s <= s["t0"] < log.end_s]


def window_wall(log) -> float:
    return log.end_s - log.open_s


def due_in_window(log) -> list:
    """The window's requests (``traffic.segments``): the same number on
    every seed.  In a traced run, those due before the trace."""
    return [q.rid for q in log.schedule
            if q.window and q.due_s < log.end_s]


def ttft(log) -> np.ndarray:
    """Time to first token of every request due in the window, from when
    it was due; one with no first token by the window's end counts at
    its age then."""
    out = []
    for rid in due_in_window(log):
        toks = log.token_s[rid]
        first = toks[0] if toks and toks[0] <= log.end_s else log.end_s
        out.append(first - log.schedule[rid].due_s)
    return np.asarray(out, float)


def inter_token_gaps(log) -> np.ndarray:
    """Every gap between two tokens of one request whose later token came
    in the window."""
    out = []
    for toks in log.token_s:
        t = np.asarray(toks, float)
        if t.size > 1:
            g = np.diff(t)
            out.append(g[(t[1:] > log.open_s) & (t[1:] <= log.end_s)])
    return np.concatenate(out) if out else np.zeros(0)


def tokens_in_window(log) -> int:
    return int(sum(np.count_nonzero((np.asarray(t) > log.open_s)
                                    & (np.asarray(t) <= log.end_s))
                   for t in log.token_s if t))


def decode_steps(log) -> list:
    """Window steps that only decoded (no prefill chunk)."""
    return [s for s in window_steps(log)
            if s["prefill_tokens"] == 0 and s["decode_ctx"]]


def attempted_failed(log) -> tuple[int, int]:
    due = [q.rid for q in log.schedule if q.window]
    return len(due), int(sum(bool(log.failed[r]) for r in due))


def percentile(values, q: float):
    values = np.asarray(values, float)
    return float(np.percentile(values, q)) if values.size else None
