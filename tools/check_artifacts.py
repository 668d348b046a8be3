"""CI artifact gate: schema-validate the checked-in benchmark trajectory
and autotune cache (ISSUE 5 satellite).

Every PR appends to ``BENCH_kernels.json`` (benchmarks/run.py) and may
regenerate ``src/repro/kernels/autotune_cache.json``
(benchmarks/autotune_serving.py).  Both are load-bearing: serving reads
the autotune cache at cold start, and the bench trajectory is the perf
baseline future PRs diff against — a malformed append (truncated JSON,
a row missing its ``us`` field, a cache value with the wrong arity) would
poison them silently.  This gate fails the build instead.

Checks (no third-party deps — stdlib json only):

* BENCH_kernels.json: top-level ``{"runs": [...]}``; every run carries a
  well-formed git rev (short/long hex or the documented 'unknown'
  fallback), an ISO-ish timestamp, and a non-empty ``rows`` list whose
  rows each have a non-empty ``name`` (str), a finite positive ``us``
  (number) and a ``derived`` (str).
* autotune_cache.json: a flat ``{key: [ints]}`` dict; keys must parse as
  a known kernel kind (``fused/`` / ``mvm/`` / ``paged_attn/``) ending in
  a cpu|tpu backend segment, and values must be positive-int tuples of
  that kind's arity (fused (bm, bn) = 2, mvm (bm, bn, bk, bl) = 4,
  paged_attn (gh, qp) = 2).
* serve/chaos_* rows (ISSUE 6): the fault-tolerance bench rows carry a
  typed derived contract — chaos_plain/chaos_monitored need a finite
  positive ``tok_s``; chaos_monitored additionally needs a positive
  ``overhead_vs_plain`` ratio (the CI-bounded fault-free monitoring
  cost) and the PageAllocator occupancy counters (``pages_live`` — zero
  at end of serve, a leak otherwise — ``pages_high_water``,
  ``pages_refusals``; pre-ISSUE-7 rows without any of them are
  grandfathered, malformed values are not); chaos_drill needs its
  scenario counters
  (``requests``, ``replays``, ``probe_trips``, ``escalations``,
  ``deadline_cancelled``) as non-negative ints.  A chaos row whose
  derived fields went missing or non-numeric would silently blind the
  regression gate.
* serve/spec_* rows (ISSUE 7): the self-speculative decoding rows need a
  finite positive ``tok_s``; the drafted rows (spec_dscim*) additionally
  need ``accepted_tok_per_verify`` (positive), ``acceptance_rate`` in
  (0, 1], and ``tokens_match=1`` — the bitwise-parity assertion baked
  into the bench; spec_continuous rows carry the allocator counters
  (``pages_live``/``pages_high_water``/``pages_refusals``) like
  chaos_monitored.
* serve/router_* rows (ISSUE 8): the async-router load-test rows
  (benchmarks/loadtest.py) need the latency percentiles
  (``p50_ms``/``p99_ms``) and ``tok_s`` finite positive,
  ``refusal_rate`` in [0, 1], the status ledger (``ok``/``deadline``/
  ``refused``/``cancelled``/``degraded``) as non-negative ints summing
  to ``requests`` (the every-request-terminates contract, checked at
  rest), ``replays``/``quarantined`` counters, and the allocator
  counters with ``pages_live=0`` — router rows are recorded after
  drain, so any live page is a leak.
* serve/integrity_* rows (ISSUE 9): the checksummed-state integrity rows
  need a finite positive ``tok_s``; integrity_scrub additionally needs a
  positive ``overhead_vs_off`` ratio (the CI-bounded scrubbing cost) and
  the sweep coverage/repair counters as non-negative ints;
  integrity_drill needs its repair/replay counters.
* serve/prefix_* rows (ISSUE 10): the prefix-cache rows carry the dedup
  ledger (``hits``/``lookups``/``hit_tokens``/``pages_deduped`` as
  non-negative ints) and ``prefill_removed_frac`` in [0, 1] — the
  CI-bounded fraction of prefill positions never computed because their
  pages were shared.  The serve-bench hit-rate sweep rows
  (prefix_hit0/hit50/hit90) additionally need a finite positive
  ``tok_s``, ``hit_rate_target`` in [0, 1], a positive
  ``admit_latency_ratio`` (hit-vs-cold admission wall time, CI-bounded
  in tools/bench_regression.py), and drained allocator occupancy
  (``pages_live=0``, ``pages_retained``/``pages_shares`` non-negative —
  retained pages are the prefix index's parked ref-0 pages, not leaks).
  The router trace row (prefix_router) rides the full serve/router_*
  schema (latency percentiles, terminal-status ledger, zero live pages)
  plus a non-negative ``bitwise_ok`` count — the number of ok-vs-ok
  request pairs asserted token-identical between the warm and cold legs.
* No duplicate rows (ISSUE 7 satellite): a row name may appear at most
  once per run, and a (name, rev) pair at most once across the whole
  trajectory — benchmarks/run.py dedupes on append (newest run wins), so
  a duplicate here means someone bypassed it and the perf diff would
  silently average two measurements.

Usage:  python tools/check_artifacts.py [--bench PATH] [--cache PATH]
Exit 0 on pass; exit 1 with one line per violation on failure.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DEFAULT = os.path.join(REPO, "BENCH_kernels.json")
CACHE_DEFAULT = os.path.join(REPO, "src", "repro", "kernels",
                             "autotune_cache.json")

_REV_RE = re.compile(r"^([0-9a-f]{7,40}|unknown)$")
_TS_RE = re.compile(r"^\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}$")
_ARITY = {"fused": 2, "mvm": 4, "paged_attn": 2}


def _derived_fields(derived: str) -> dict:
    """Parse the ``k=v;k=v`` derived string (values stay strings)."""
    out = {}
    for part in derived.split(";"):
        if "=" in part:
            k, _, v = part.partition("=")
            out[k] = v
    return out


def _pos_float(v) -> bool:
    try:
        x = float(v)
    except (TypeError, ValueError):
        return False
    return x > 0 and x == x and x != float("inf")


def _nonneg_int(v) -> bool:
    try:
        return int(v) >= 0 and float(v) == int(v)
    except (TypeError, ValueError):
        return False


def _check_chaos_row(name: str, derived: str, rtag: str, errs: list):
    """ISSUE 6: typed schema for serve/chaos_* derived fields."""
    f = _derived_fields(derived)
    kind = name.split("/", 2)[1]            # chaos_plain | _monitored | _drill
    if kind in ("chaos_plain", "chaos_monitored"):
        if not _pos_float(f.get("tok_s")):
            errs.append(f"{rtag} ({name!r}): chaos row needs a finite "
                        f"positive tok_s, got {f.get('tok_s')!r}")
    if kind == "chaos_monitored":
        if not _pos_float(f.get("overhead_vs_plain")):
            errs.append(f"{rtag} ({name!r}): chaos_monitored needs a "
                        f"positive overhead_vs_plain ratio, got "
                        f"{f.get('overhead_vs_plain')!r}")
        _check_page_stats(name, f, rtag, errs, required=False)
    if kind == "chaos_drill":
        for key in ("requests", "replays", "probe_trips", "escalations",
                    "deadline_cancelled"):
            if not _nonneg_int(f.get(key)):
                errs.append(f"{rtag} ({name!r}): chaos_drill needs "
                            f"non-negative int {key}, got {f.get(key)!r}")


def _check_page_stats(name: str, f: dict, rtag: str, errs: list,
                      required: bool = True):
    """PageAllocator.stats() counters on continuous-serving rows.
    ``required=False`` grandfathers pre-ISSUE-7 rows that predate the
    counters: absent is tolerated, present-but-malformed is not."""
    keys = ("pages_live", "pages_high_water", "pages_refusals")
    if not required and not any(k in f for k in keys):
        return
    for key in keys:
        if not _nonneg_int(f.get(key)):
            errs.append(f"{rtag} ({name!r}): needs non-negative int "
                        f"{key} (PageAllocator.stats()), got "
                        f"{f.get(key)!r}")


def _check_spec_row(name: str, derived: str, rtag: str, errs: list):
    """ISSUE 7: typed schema for serve/spec_* derived fields."""
    f = _derived_fields(derived)
    kind = name.split("/", 2)[1]     # spec_off | spec_dscim2_k<k> | spec_...
    if not _pos_float(f.get("tok_s")):
        errs.append(f"{rtag} ({name!r}): spec row needs a finite positive "
                    f"tok_s, got {f.get('tok_s')!r}")
    if kind.startswith("spec_dscim"):
        if not _pos_float(f.get("accepted_tok_per_verify")):
            errs.append(f"{rtag} ({name!r}): drafted spec row needs a "
                        f"positive accepted_tok_per_verify, got "
                        f"{f.get('accepted_tok_per_verify')!r}")
        try:
            rate = float(f.get("acceptance_rate"))
        except (TypeError, ValueError):
            rate = -1.0
        if not 0.0 < rate <= 1.0:
            errs.append(f"{rtag} ({name!r}): acceptance_rate must be in "
                        f"(0, 1], got {f.get('acceptance_rate')!r}")
        if f.get("tokens_match") != "1":
            errs.append(f"{rtag} ({name!r}): drafted spec row must assert "
                        f"bitwise parity (tokens_match=1), got "
                        f"{f.get('tokens_match')!r}")
    if kind == "spec_continuous":
        _check_page_stats(name, f, rtag, errs)


def _check_integrity_row(name: str, derived: str, rtag: str, errs: list):
    """ISSUE 9: typed schema for serve/integrity_* derived fields
    (benchmarks/serve_bench.py ``_integrity_rows``).  integrity_off /
    integrity_scrub need a finite positive ``tok_s``; integrity_scrub
    additionally needs a positive ``overhead_vs_off`` ratio (the
    CI-bounded scrubbing cost) and the sweep coverage counters;
    integrity_drill needs its repair/replay counters — a drill row whose
    counters went missing would silently blind the self-healing gate."""
    f = _derived_fields(derived)
    kind = name.split("/", 2)[1]   # integrity_off | _scrub | _drill
    if kind in ("integrity_off", "integrity_scrub"):
        if not _pos_float(f.get("tok_s")):
            errs.append(f"{rtag} ({name!r}): integrity row needs a finite "
                        f"positive tok_s, got {f.get('tok_s')!r}")
    if kind == "integrity_scrub":
        if not _pos_float(f.get("overhead_vs_off")):
            errs.append(f"{rtag} ({name!r}): integrity_scrub needs a "
                        f"positive overhead_vs_off ratio, got "
                        f"{f.get('overhead_vs_off')!r}")
        for key in ("checks", "pages_verified", "weight_planes_verified",
                    "mismatches", "repairs"):
            if not _nonneg_int(f.get(key)):
                errs.append(f"{rtag} ({name!r}): integrity_scrub needs "
                            f"non-negative int {key}, got {f.get(key)!r}")
    if kind == "integrity_drill":
        for key in ("requests", "page_repairs", "weight_repairs",
                    "replays", "checks"):
            if not _nonneg_int(f.get(key)):
                errs.append(f"{rtag} ({name!r}): integrity_drill needs "
                            f"non-negative int {key}, got {f.get(key)!r}")


def _check_prefix_row(name: str, derived: str, rtag: str, errs: list):
    """ISSUE 10: typed schema for serve/prefix_* derived fields.  All
    prefix rows carry the dedup ledger and the removed-prefill fraction;
    the serve-bench sweep rows (prefix_hit*) add the admission-latency
    ratio and drained allocator occupancy, and the loadtest row
    (prefix_router) layers the prefix ledger on the full router-row
    schema plus the bitwise ok-vs-ok assertion count.  A prefix row
    whose ledger went missing would blind both CI bounds (the
    flops-removed floor and the hit-admission latency ceiling)."""
    f = _derived_fields(derived)
    kind = name.split("/", 2)[1]    # prefix_hit0|hit50|hit90|router
    for key in ("hits", "lookups", "hit_tokens", "pages_deduped"):
        if not _nonneg_int(f.get(key)):
            errs.append(f"{rtag} ({name!r}): prefix row needs non-negative "
                        f"int {key}, got {f.get(key)!r}")
    try:
        removed = float(f.get("prefill_removed_frac"))
    except (TypeError, ValueError):
        removed = -1.0
    if not 0.0 <= removed <= 1.0:
        errs.append(f"{rtag} ({name!r}): prefill_removed_frac must be in "
                    f"[0, 1], got {f.get('prefill_removed_frac')!r}")
    if kind == "prefix_router":
        _check_router_row(name, derived, rtag, errs)
        if not _nonneg_int(f.get("bitwise_ok")):
            errs.append(f"{rtag} ({name!r}): prefix_router needs a "
                        f"non-negative int bitwise_ok (ok-vs-ok pairs "
                        f"asserted token-identical), got "
                        f"{f.get('bitwise_ok')!r}")
        if not _nonneg_int(f.get("pages_retained")):
            errs.append(f"{rtag} ({name!r}): prefix_router needs "
                        f"non-negative int pages_retained, got "
                        f"{f.get('pages_retained')!r}")
    else:
        if not _pos_float(f.get("tok_s")):
            errs.append(f"{rtag} ({name!r}): prefix row needs a finite "
                        f"positive tok_s, got {f.get('tok_s')!r}")
        try:
            target = float(f.get("hit_rate_target"))
        except (TypeError, ValueError):
            target = -1.0
        if not 0.0 <= target <= 1.0:
            errs.append(f"{rtag} ({name!r}): hit_rate_target must be in "
                        f"[0, 1], got {f.get('hit_rate_target')!r}")
        if not _pos_float(f.get("admit_latency_ratio")):
            errs.append(f"{rtag} ({name!r}): prefix sweep row needs a "
                        f"positive admit_latency_ratio, got "
                        f"{f.get('admit_latency_ratio')!r}")
        for key in ("pages_retained", "pages_shares"):
            if not _nonneg_int(f.get(key)):
                errs.append(f"{rtag} ({name!r}): prefix sweep row needs "
                            f"non-negative int {key}, got {f.get(key)!r}")
        if f.get("pages_live") != "0":
            errs.append(f"{rtag} ({name!r}): prefix rows are recorded "
                        f"after drain — pages_live must be 0, got "
                        f"{f.get('pages_live')!r} (page leak)")


def _check_router_row(name: str, derived: str, rtag: str, errs: list):
    """ISSUE 8: typed schema for serve/router_* load-test rows
    (benchmarks/loadtest.py).  Every row must carry the latency
    percentiles, throughput, a refusal rate in [0, 1], the request/status
    ledger (statuses summing to requests — a request that vanished
    without a terminal status would break the sum), and drained page-pool
    counters with pages_live == 0."""
    f = _derived_fields(derived)
    for key in ("p50_ms", "p99_ms", "tok_s"):
        if not _pos_float(f.get(key)):
            errs.append(f"{rtag} ({name!r}): router row needs a finite "
                        f"positive {key}, got {f.get(key)!r}")
    try:
        rate = float(f.get("refusal_rate"))
    except (TypeError, ValueError):
        rate = -1.0
    if not 0.0 <= rate <= 1.0:
        errs.append(f"{rtag} ({name!r}): refusal_rate must be in [0, 1], "
                    f"got {f.get('refusal_rate')!r}")
    statuses = ("ok", "deadline", "refused", "cancelled", "degraded")
    for key in ("requests", "replays", "quarantined") + statuses:
        if not _nonneg_int(f.get(key)):
            errs.append(f"{rtag} ({name!r}): router row needs non-negative "
                        f"int {key}, got {f.get(key)!r}")
    try:
        if sum(int(f[s]) for s in statuses) != int(f["requests"]):
            errs.append(f"{rtag} ({name!r}): terminal statuses must sum to "
                        f"requests (every request ends definitely), got "
                        + ";".join(f"{s}={f[s]}" for s in statuses)
                        + f" vs requests={f['requests']}")
    except (KeyError, TypeError, ValueError):
        pass                        # already reported above
    _check_page_stats(name, f, rtag, errs)
    if f.get("pages_live") not in (None, "0"):
        errs.append(f"{rtag} ({name!r}): router rows are recorded after "
                    f"drain — pages_live must be 0, got "
                    f"{f.get('pages_live')!r} (page leak)")


def _load(path: str, errs: list) -> object | None:
    if not os.path.exists(path):
        errs.append(f"{path}: missing")
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except ValueError as e:
        errs.append(f"{path}: not valid JSON ({e})")
        return None


def check_bench(path: str) -> list:
    errs: list = []
    data = _load(path, errs)
    if data is None:
        return errs
    if not isinstance(data, dict) or not isinstance(data.get("runs"), list):
        return [f"{path}: top level must be {{'runs': [...]}}"]
    seen_rev_name: dict = {}
    for i, run in enumerate(data["runs"]):
        tag = f"{path}: runs[{i}]"
        if not isinstance(run, dict):
            errs.append(f"{tag}: not an object")
            continue
        rev = run.get("rev")
        if not (isinstance(rev, str) and _REV_RE.match(rev)):
            errs.append(f"{tag}: bad rev {rev!r}")
        ts = run.get("ts")
        if not (isinstance(ts, str) and _TS_RE.match(ts)):
            errs.append(f"{tag}: bad ts {ts!r}")
        rows = run.get("rows")
        if not (isinstance(rows, list) and rows):
            errs.append(f"{tag}: rows must be a non-empty list")
            continue
        in_run: set = set()
        for j, row in enumerate(rows):
            rtag = f"{tag}.rows[{j}]"
            if not isinstance(row, dict):
                errs.append(f"{rtag}: not an object")
                continue
            name = row.get("name")
            if not (isinstance(name, str) and name.strip()):
                errs.append(f"{rtag}: bad name {name!r}")
            elif name in in_run:
                errs.append(f"{rtag}: duplicate row {name!r} within the run")
            else:
                in_run.add(name)
                key = (run.get("rev"), name)
                if key in seen_rev_name:
                    errs.append(f"{rtag}: duplicate (name, rev) "
                                f"({name!r}, {run.get('rev')!r}) — already "
                                f"in {seen_rev_name[key]}; "
                                "benchmarks/run.py dedupes on append")
                else:
                    seen_rev_name[key] = tag
            us = row.get("us")
            if not (isinstance(us, (int, float)) and not isinstance(us, bool)
                    and us > 0 and us == us and us != float("inf")):
                errs.append(f"{rtag} ({name!r}): bad us {us!r}")
            derived = row.get("derived")
            if not isinstance(derived, str):
                errs.append(f"{rtag} ({name!r}): bad derived {derived!r}")
            elif isinstance(name, str) and name.startswith("serve/chaos_"):
                _check_chaos_row(name, derived, rtag, errs)
            elif isinstance(name, str) and name.startswith("serve/spec_"):
                _check_spec_row(name, derived, rtag, errs)
            elif isinstance(name, str) and name.startswith("serve/router_"):
                _check_router_row(name, derived, rtag, errs)
            elif isinstance(name, str) and name.startswith("serve/prefix_"):
                _check_prefix_row(name, derived, rtag, errs)
            elif isinstance(name, str) \
                    and name.startswith("serve/integrity_"):
                _check_integrity_row(name, derived, rtag, errs)
    return errs


def check_cache(path: str) -> list:
    errs: list = []
    data = _load(path, errs)
    if data is None:
        return errs
    if not isinstance(data, dict):
        return [f"{path}: top level must be an object"]
    for key, val in data.items():
        tag = f"{path}: {key!r}"
        kind = str(key).split("/", 1)[0]
        if kind not in _ARITY:
            errs.append(f"{tag}: unknown kernel kind {kind!r} "
                        f"(want one of {sorted(_ARITY)})")
            continue
        if str(key).rsplit("/", 1)[-1] not in ("cpu", "tpu"):
            errs.append(f"{tag}: key must end in a cpu|tpu backend segment")
        if not (isinstance(val, list)
                and len(val) == _ARITY[kind]
                and all(isinstance(v, int) and not isinstance(v, bool)
                        and v > 0 for v in val)):
            errs.append(f"{tag}: value {val!r} must be {_ARITY[kind]} "
                        "positive ints")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default=BENCH_DEFAULT)
    ap.add_argument("--cache", default=CACHE_DEFAULT)
    args = ap.parse_args(argv)
    errs = check_bench(args.bench) + check_cache(args.cache)
    for e in errs:
        print(f"ARTIFACT ERROR: {e}", file=sys.stderr)
    if not errs:
        print(f"artifacts OK: {args.bench}, {args.cache}")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
