"""End-to-end arithmetic on the host clock: what each request saw."""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

MISSING = math.inf      # a failed or refused request misses every limit


@dataclasses.dataclass
class Seen:
    """One request as the harness saw it, times in seconds on one clock."""
    rid: int
    due: float
    submitted: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    failed: bool = False


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default), over every value given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[lo] == xs[hi]:
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttfts(seen: Sequence[Seen], t0: float, t1: float) -> List[float]:
    """Time to first token of every request due in [t0, t1), from its due
    time.  A request with no token by t1 counts at its wait so far; a
    failed one counts as missing."""
    out = []
    for r in seen:
        if not t0 <= r.due < t1:
            continue
        if r.failed:
            out.append(MISSING)
        elif r.token_times and r.token_times[0] <= t1:
            out.append(r.token_times[0] - r.due)
        else:
            out.append(t1 - r.due)
    return out


def gaps_in_window(seen: Sequence[Seen], t0: float, t1: float) -> List[float]:
    """Every inter-token gap whose two tokens both fall in [t0, t1]."""
    out = []
    for r in seen:
        ts = [t for t in r.token_times if t0 <= t <= t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(seen: Sequence[Seen], t0: float, t1: float) -> int:
    return sum(1 for r in seen for t in r.token_times if t0 <= t <= t1)


def end_to_end(seen: Sequence[Seen], t0: float, t1: float
               ) -> Dict[str, float]:
    """The window's end-to-end numbers (the caller keeps those its cell
    reports).  Rates are over the whole window's length."""
    out = {"output_tok_s": tokens_in_window(seen, t0, t1) / (t1 - t0)}
    gaps = gaps_in_window(seen, t0, t1)
    if gaps:
        out["itl_p50_ms"] = 1e3 * percentile(gaps, 50)
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    tt = ttfts(seen, t0, t1)
    if tt:
        out["ttft_p85_ms"] = 1e3 * percentile(tt, 85)
    return out


def counts(seen: Sequence[Seen], t0: float, t1: float) -> Dict[str, int]:
    due = [r for r in seen if t0 <= r.due < t1]
    return {"attempted": len(due), "failed": sum(r.failed for r in due),
            "first_token_in_window": sum(
                1 for r in due if r.token_times and r.token_times[0] <= t1)}
