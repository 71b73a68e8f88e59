"""End-to-end metric arithmetic, on plain lists of host-clock readings.

A serving run is a list of ``Step`` records and a list of ``Request``
records; a training run is a list of step end times. Nothing here reads the
program: the numbers are what the benchmark's own loop saw from outside.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in 0..100."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


@dataclass
class Step:
    """One engine step as the closed loop saw it."""
    t0: float                  # host clock before server.step()
    t1: float                  # after the step's tokens were read back
    decode_rows: int           # requests that already had a token and got one
    prefill_tokens: int        # prompt tokens advanced (cache-adopted included)
    emitted: int               # tokens that appeared in this step
    blocks_live: int = 0       # pool blocks referenced by live requests
    decode_pages: int = 0      # pages the decode rows attended over

    @property
    def tokens(self) -> int:
        return self.prefill_tokens + self.emitted

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


@dataclass
class Request:
    prompt_len: int
    out_len: int
    t_submit: float
    token_times: list = field(default_factory=list)   # one per output token


def serve_metrics(steps, requests, t_open: float) -> dict:
    """End-to-end serving metrics over the window that opens at ``t_open``
    and closes with the last step: all the work and all the time."""
    win = [s for s in steps if s.t1 > t_open]
    if not win:
        raise ValueError("no step ended inside the window")
    t_close = win[-1].t1
    out = {"serve_tok_per_s": sum(s.tokens for s in win) / (t_close - t_open)}
    ttft = [(r.token_times[0] - r.t_submit) * 1e3 for r in requests
            if r.token_times and r.token_times[0] > t_open]
    gaps = [(b - a) * 1e3 for r in requests
            for a, b in zip(r.token_times, r.token_times[1:]) if b > t_open]
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50)
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95)
    out["_samples"] = {"steps": len(win), "ttft": len(ttft), "gaps": len(gaps)}
    return out


def train_metrics(step_ends, tokens_per_step: int, t_open: float) -> dict:
    """Tokens of the steps that ended in the window over the window."""
    win = [t for t in step_ends if t > t_open]
    if not win:
        raise ValueError("no step ended inside the window")
    return {"train_tok_per_s": len(win) * tokens_per_step / (win[-1] - t_open),
            "_samples": {"steps": len(win)}}
