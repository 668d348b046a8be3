"""End-to-end metrics of one window, from the load client's records."""
from __future__ import annotations

import numpy as np


def p95(values) -> float | None:
    return float(np.percentile(values, 95)) if len(values) else None


def window_metrics(records, w0: float, w1: float) -> dict:
    """``records``: load.Record list; ``[w0, w1)``: the measured window on
    the host clock.

    * attempted: requests due (open loop) or sent (closed loop) in the
      window; failed: those refused or ended with another status than ok;
    * ttft: due time to first streamed token; a request still without a
      token at the window's end (or refused) enters as ``w1 - due``;
    * tpot: per request with at least two tokens inside the window,
      (last - first) / (tokens - 1) over those tokens;
    * output_tok_s: every token streamed inside the window, over its
      length.
    """
    due = [r for r in records if w0 <= r.due < w1]
    failed = sum(1 for r in due
                 if r.status is not None and r.status != "ok")
    ttft = []
    for r in due:
        first = r.times[0] if r.times else None
        ttft.append((first if first is not None and first < w1 else w1)
                    - r.due)
    tpot = []
    n_tok = 0
    for r in records:
        ts = [t for t in r.times if w0 <= t < w1]
        n_tok += len(ts)
        if len(ts) >= 2:
            tpot.append((ts[-1] - ts[0]) / (len(ts) - 1))
    late = [r.sent - r.due for r in due if r.sent is not None]
    return {
        "attempted": len(due), "failed": failed,
        "ttft_p95_ms": None if not ttft else 1e3 * p95(ttft),
        "tpot_p95_ms": None if not tpot else 1e3 * p95(tpot),
        "output_tok_s": n_tok / (w1 - w0),
        "n_ttft": len(ttft), "n_tpot": len(tpot), "tokens": n_tok,
        "late_p95_ms": None if not late else 1e3 * p95(late),
        "late_max_ms": None if not late else 1e3 * max(late),
    }


def token_bins(records, t0: float, t1: float, bin_s: float) -> list:
    """Tokens streamed in each ``bin_s`` interval of ``[t0, t1)``: whether
    the rate is steady across and before the window."""
    n = max(1, int((t1 - t0) // bin_s))
    bins = [0] * n
    for r in records:
        for t in r.times:
            i = int((t - t0) // bin_s)
            if 0 <= i < n:
                bins[i] += 1
    return bins
