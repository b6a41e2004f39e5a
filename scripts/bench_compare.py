#!/usr/bin/env python
"""Bench-trajectory regression guard: compare the newest BENCH_r*.json
round against the previous one per metric name.

The BENCH_r<NN>.json files are the committed per-round driver captures
(config-2 bench.py child): ``tail`` holds the child's raw stdout —
including every ``{"metric": ...}`` JSON line — and ``parsed`` the last
metric line.  Nothing guarded that trajectory against silent perf
regressions: a round could land 30% slower and nobody would notice until
a human re-read the table.  This script makes the comparison mechanical:

- extract every metric line from each round (plus the headline's
  ``true_rate``/``p99_ms`` companions as ``<metric>.true_rate`` /
  ``<metric>.p99_ms`` — the honest numbers ride as extra fields);
- compare the newest round with metrics against the previous such round,
  direction-aware (units/suffixes decide whether bigger is better);
- print a one-line-per-metric trajectory table;
- exit nonzero when any metric regressed beyond ``--tolerance``
  (default 10%) — ``run_all.py --compare`` wires this as the suite's
  final gate.

New metrics (no previous value) and retired metrics are reported but
never fail the run; platform changes between rounds are noted (a cpu
round vs a tpu round is apples vs oranges — flagged, not failed).
A higher-better row whose own ``roofline_frac`` is within tolerance of
1.0 is flagged ``host-bound`` instead of failed: the kernel is at the
measured memory-bandwidth ceiling of THIS host, so no software change
can close the gap — the delta is the box (rounds run on whatever
container the driver got; the triad ceiling is the host fingerprint).

Usage:
  python scripts/bench_compare.py [--dir /root/repo] [--tolerance 0.10]
                                  [--old r04] [--new r05]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

#: units where a SMALLER value is the better one
_LOWER_BETTER_UNITS = {"ms", "s", "seconds", "mb", "mib", "bytes", "gb"}
#: metric-name suffixes that mark lower-better numbers regardless of unit
#: (``pad_fraction``: the perf ledger's wasted-lanes share)
#: (``explain_overhead_frac``: the armed decision-log median shift on
#: the client check path as a fraction of its p99, from the smoke's
#: interleaved-rep A/B — growing means provenance is creeping into the
#: serving budget; ``decisions_dropped``: decision-log entries lost to
#: sink failures — any growth is an audit-trail hole;
#: ``dispatches_per_lookup``: device program launches per LookupResources
#: drain from bench8 — the fused SpMM path's whole point is holding this
#: at 1.0, so any growth is the K-hop fusion regressing to per-hop loops;
#: ``pad_waste_frac``: bench11's padded-lane share under the tuned config
#: — the tuner's tier ladder exists to shrink it, so growth means the
#: ladder rules stopped fitting the workload;
#: ``probe_depth_after_compaction``: bench12's residual delta-chain
#: overlay rows with the background compactor on — growth means the
#: compactor stopped keeping probe depth bounded and writers are headed
#: back toward the synchronous O(E) merge)
_LOWER_BETTER_SUFFIXES = (
    "_ms", "_s", "_latency", "_bytes", "_rss_mb", "pad_fraction",
    "explain_overhead_frac", "decisions_dropped", "dispatches_per_lookup",
    "pad_waste_frac", "probe_depth_after_compaction",
)
#: suffixes that are HIGHER-better regardless of unit — checked FIRST,
#: so the perf columns can't be misread by a unit heuristic
#: (``achieved_gbps`` must not fall into the "gb" lower-better unit
#: bucket; ``roofline_frac`` closer to the ceiling is the win;
#: ``hit_rate``/``dedup_frac`` are the verdict-cache columns — a round
#: that serves fewer checks from cache/dedup at the same workload has
#: regressed, and ``_frac``'s trailing "_s" must not read as seconds)
#: (``mixed_users_rate`` is candidates/sec over bench8's 48 small-reach
#: users — the dispatch-floor workload the fused SpMM path exists for;
#: its trailing "_rate" must never read as anything but higher-better)
#: (``fleet_goodput_scaling`` is the N-replica/1-replica goodput ratio
#: from bench10 — more replicas helping more is the win, and its value
#: is an "x" multiplier, not a latency; ``failover_p99_ms`` stays
#: lower-better via the ``_ms`` suffix and is listed in
#: ``_PROMOTED_FIELDS`` so rows carrying it as a column also guard it)
#: (``tuned_vs_best_preset_goodput`` is bench11's geomean goodput ratio
#: of the tuned config over the best preset per profile — an "x"
#: multiplier like fleet scaling; below 1.0 the tuner stopped paying)
#: (``writes_per_s`` covers bench12's ``writes_per_s`` and
#: ``committer_writes_per_s`` — write throughput must be read
#: higher-better even though the raw "_s" suffix would otherwise flag
#: it as a latency; ``group_size_p50`` is bench12's achieved
#: writes-per-group median — shrinking groups mean the committer
#: stopped coalescing and every revision pays its machinery alone)
_HIGHER_BETTER_SUFFIXES = (
    "achieved_gbps", "roofline_frac", "hit_rate", "dedup_frac",
    "cache_speedup", "mixed_users_rate", "fleet_goodput_scaling",
    "tuned_vs_best_preset_goodput", "writes_per_s", "group_size_p50",
)
#: extra fields of a metric line promoted to their own comparison rows
#: (the perf-attribution columns ride headline rows as extra fields —
#: promoting them guards the roofline trajectory from round one)
#: (``dedup_frac`` is direction-registered above but NOT promoted: its
#: absolute value is workload-noise-sized on the uniform-window bench,
#: and a 0.0003→0.0001 wiggle must not fail a round)
_PROMOTED_FIELDS = (
    "true_rate", "p99_ms", "achieved_gbps", "roofline_frac", "pad_fraction",
    "cache_hit_rate", "explain_overhead_frac", "decisions_dropped",
    "mixed_users_rate", "dispatches_per_lookup", "failover_p99_ms",
)
#: boolean/one-shot rows that carry no trajectory signal
_SKIP_UNITS = {"ok", "capture", "keys"}


def lower_is_better(name: str, unit: str) -> bool:
    if any(name.endswith(s) for s in _HIGHER_BETTER_SUFFIXES):
        return False
    u = unit.strip().lower()
    if u in _LOWER_BETTER_UNITS:
        return True
    if any(name.endswith(s) for s in _LOWER_BETTER_SUFFIXES):
        return True
    return False


def metrics_of(path: str) -> dict:
    """metric name → {value, unit, platform} from one BENCH_r file
    (every JSON metric line in ``tail``, newest wins, plus ``parsed``)."""
    with open(path) as f:
        doc = json.load(f)
    out: dict = {}

    def take(parsed) -> None:
        if not isinstance(parsed, dict) or "metric" not in parsed:
            return
        name = parsed["metric"]
        unit = str(parsed.get("unit", ""))
        if unit in _SKIP_UNITS:
            return
        try:
            value = float(parsed.get("value"))
        except (TypeError, ValueError):
            return
        plat = parsed.get("platform", "")
        rf = parsed.get("roofline_frac")
        rf = float(rf) if isinstance(rf, (int, float)) else None
        out[name] = {
            "value": value, "unit": unit, "platform": plat,
            "roofline_frac": rf,
        }
        for fld in _PROMOTED_FIELDS:
            v = parsed.get(fld)
            if isinstance(v, (int, float)):
                out[f"{name}.{fld}"] = {
                    "value": float(v),
                    "unit": "ms" if fld.endswith("ms") else unit,
                    "platform": plat,
                    #: promoted companions share the parent row's kernel
                    "roofline_frac": rf,
                }

    for line in (doc.get("tail") or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                take(json.loads(line))
            except json.JSONDecodeError:
                continue
    take(doc.get("parsed"))
    return out


def round_key(path: str) -> int:
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else -1


def compare(
    old: dict, new: dict, old_name: str, new_name: str, tolerance: float
):
    """Returns (table rows, regression count).  A row is one formatted
    line; regressions are direction-aware changes beyond tolerance."""
    rows = []
    regressions = 0
    width = max([len(n) for n in set(old) | set(new)] + [6])
    for name in sorted(set(old) | set(new)):
        o, n = old.get(name), new.get(name)
        if o is None:
            rows.append(f"{name:<{width}}  {'—':>12} -> {n['value']:>12,.1f}"
                        f"  {'new':>8}  {n['unit']}")
            continue
        if n is None:
            rows.append(f"{name:<{width}}  {o['value']:>12,.1f} -> {'—':>12}"
                        f"  {'gone':>8}")
            continue
        ov, nv = o["value"], n["value"]
        if ov == 0:
            delta = 0.0 if nv == 0 else float("inf")
        else:
            delta = (nv - ov) / abs(ov)
        lower = lower_is_better(name, n["unit"] or o["unit"])
        worse = -delta if lower else delta
        rf = n.get("roofline_frac")
        if o.get("platform") and n.get("platform") and (
            o["platform"] != n["platform"]
        ):
            verdict = f"platform {o['platform']}->{n['platform']}"
        elif (
            worse < -tolerance and not lower
            and rf is not None and rf >= 1.0 - tolerance
        ):
            # the new round measures at the memory-bandwidth ceiling of
            # its own host — a throughput drop from there is the box,
            # not the code (lower-better rows get no such excuse: a
            # latency row can always regress by software)
            verdict = f"host-bound ({rf:.2f} of ceiling)"
        elif worse < -tolerance:
            verdict = "REGRESSED"
            regressions += 1
        elif worse > tolerance:
            verdict = "improved"
        else:
            verdict = "ok"
        rows.append(
            f"{name:<{width}}  {ov:>12,.1f} -> {nv:>12,.1f}"
            f"  {delta:>+7.1%}  {verdict}"
        )
    header = (
        f"{'metric':<{width}}  {old_name:>12} -> {new_name:>12}"
        f"  {'delta':>8}  verdict"
    )
    return [header] + rows, regressions


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--dir",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    ap.add_argument("--glob", default="BENCH_r*.json")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="relative worsening tolerated before failing")
    ap.add_argument("--old", default=None,
                    help="explicit old round (e.g. r04); default: previous"
                         " round with metrics")
    ap.add_argument("--new", default=None,
                    help="explicit new round (e.g. r05); default: newest"
                         " round with metrics")
    args = ap.parse_args()

    paths = sorted(glob.glob(os.path.join(args.dir, args.glob)),
                   key=round_key)
    if len(paths) < 2:
        print(f"bench_compare: fewer than two rounds match "
              f"{args.glob} under {args.dir} — nothing to compare")
        return 0

    def named(tag):
        for p in paths:
            if os.path.basename(p) == f"BENCH_{tag}.json" or (
                f"_{tag}." in os.path.basename(p)
            ):
                return p
        print(f"bench_compare: no round named {tag}", file=sys.stderr)
        return None

    if args.new is not None:
        new_path = named(args.new)
        if new_path is None:
            return 2
    else:
        new_path = None
    if args.old is not None:
        old_path = named(args.old)
        if old_path is None:
            return 2
    else:
        old_path = None

    # walk newest→oldest picking the two most recent rounds that carry
    # metrics at all (a probe-failed round records rc/tail but no JSON
    # metric lines — skipping it keeps the comparison meaningful)
    usable = [(p, metrics_of(p)) for p in paths]
    with_metrics = [(p, m) for p, m in usable if m]
    if new_path is None:
        if not with_metrics:
            print("bench_compare: no round carries metrics")
            return 0
        new_path, new_metrics = with_metrics[-1]
    else:
        new_metrics = metrics_of(new_path)
    if old_path is None:
        older = [(p, m) for p, m in with_metrics
                 if round_key(p) < round_key(new_path)]
        if not older:
            print(f"bench_compare: no earlier round with metrics before "
                  f"{os.path.basename(new_path)}")
            return 0
        old_path, old_metrics = older[-1]
    else:
        old_metrics = metrics_of(old_path)

    short = lambda p: os.path.basename(p).replace("BENCH_", "").replace(
        ".json", ""
    )
    rows, regressions = compare(
        old_metrics, new_metrics, short(old_path), short(new_path),
        args.tolerance,
    )
    for r in rows:
        print(r)
    if regressions:
        print(f"\nbench_compare: {regressions} metric(s) regressed beyond "
              f"{args.tolerance:.0%} ({short(old_path)} -> "
              f"{short(new_path)})")
        return 1
    print(f"\nbench_compare: trajectory ok "
          f"({short(old_path)} -> {short(new_path)}, "
          f"tolerance {args.tolerance:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
