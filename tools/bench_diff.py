#!/usr/bin/env python
"""bench_diff — fail-loud regression sentinel over the bench trajectory.

The repo accumulates one ``BENCH_r<NN>.json`` artifact per round
(``{"n", "cmd", "rc", "tail", "parsed"}``, where ``parsed`` is the
bench's JSON line), but until now nothing consumed the trajectory — a
regression only surfaced if a human diffed two rounds by hand. This
tool compares the newest round against the previous one per headline
metric and **exits nonzero** when a metric crosses its threshold:

- throughput headline (``value`` in tokens/s/chip, or any
  higher-is-better unit): min ratio 0.85 — a >15% drop fails;
- any ``ms``-unit headline (lower is better): max ratio 1.18;
- ``mfu`` / ``engine_mfu``: min ratio 0.85;
- ``hidden_comm_frac``: max absolute drop 0.15 (overlap regressions);
- ``host_gap_ms``: max ratio 1.5 (noisy on a shared host — loose);
- quantization gates (``BENCH_QUANT`` payloads): the new round's
  ``ok`` flag must be true and ``value`` (gate violations) must not
  grow — the quant SNR gates re-checked at diff time.

Rounds with a different metric/unit (the headline changed shape, e.g.
zero3 train → device fwd+bwd) are *incomparable*: reported, but only a
failure under ``--strict``. Contended rounds (``contended: true``)
loosen throughput thresholds by 10% — the shared 1-core host's loadavg
sentinel already marks them as noisy.

Usage:
  python tools/bench_diff.py                # newest vs previous round
  python tools/bench_diff.py --root . --json
  python tools/bench_diff.py --old BENCH_r04.json --new BENCH_r05.json
  make bench-diff
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCHEMA = "bench_diff/v1"

# (metric key, direction, default threshold). Ratios are new/old:
# "min_ratio" fails when new/old < t (higher is better); "max_ratio"
# fails when new/old > t (lower is better); "max_drop" fails when
# old - new > t (absolute units).
DEFAULT_THRESHOLDS: Dict[str, Tuple[str, float]] = {
    "value_higher": ("min_ratio", 0.85),
    "value_lower": ("max_ratio", 1.18),
    "mfu": ("min_ratio", 0.85),
    "engine_mfu": ("min_ratio", 0.85),
    "hidden_comm_frac": ("max_drop", 0.15),
    "host_gap_ms": ("max_ratio", 1.5),
    # serving-quant arm (BENCH_MODE=serve_quant): wire compression must
    # not erode >10% between rounds, the measured wire SNR must not drop
    # >3 dB, and each arm's concurrent-session capacity holds like any
    # other throughput headline
    "handoff_wire_frac": ("max_ratio", 1.1),
    "handoff_wire_snr_db": ("max_drop", 3.0),
    "sessions_capacity": ("min_ratio", 0.85),
    # cross-process fleet (BENCH_MODE=serve_procs): the int4 KV wire
    # must stay compressed round-over-round, and the chaos arm's tail
    # latency under a mid-run SIGKILL gets a loose leash — p99.9 of a
    # small open-loop run is one request's failover, so only a >1.5x
    # blowup (a broken failover path, not scheduling noise) fails
    "kv_wire_ratio": ("max_ratio", 1.15),
    "ttft_p999_ms": ("max_ratio", 1.5),
    # chaos-certified fleet (BENCH_MODE=chaos_fleet): the worst
    # fault-arm p99.9 TTFT relative to the fault-free arm may not grow
    # >1.5x round-over-round (a slower recovery path), and the boolean
    # chaos.zero_drops / chaos.bit_identical certificates must stay
    # true — those are checked unconditionally below, not ratio'd
    "chaos.ttft_p999_ratio": ("max_ratio", 1.5),
    # observability plane (BENCH_MODE=obs_fleet): the per-request tracer
    # emit-point overhead gets a loose order-of-magnitude leash (tens of
    # µs measured on a shared host — only a blowup is signal), and the
    # worst clock-offset error may not grow by more than 5 ms absolute;
    # the boolean obs.trace_overhead_ok / obs.offset_bound_ok
    # certificates are checked unconditionally below
    "obs.trace_overhead_us": ("max_ratio", 3.0),
    "obs.offset_err_ms": ("max_increase", 5.0),
    # tiered-KV arm (BENCH_MODE=serve_tier): sessions held per HBM GB
    # is a capacity headline like any throughput number, the
    # warm-resume TTFT ratio may not drift back toward re-prefill cost,
    # and the distilled drafter's accept rate must not quietly erode
    # (its hard >=1.05x-vs-lookup edge gate rides quant_gates below)
    "tier.sessions_per_gb": ("min_ratio", 0.85),
    "tier.warm_resume_ttft_ratio": ("max_ratio", 1.25),
    "spec.accept_rate": ("min_ratio", 0.9),
    # fleet black box (BENCH_MODE=replay_fleet): journal append overhead
    # and journal bytes per request may not silently balloon — a record
    # kind that grew a verbose field shows up here before it shows up as
    # a serving regression; the boolean replay.bit_identical certificate
    # is checked unconditionally below
    "replay.journal_overhead_us": ("max_ratio", 3.0),
    "replay.journal_bytes_per_request": ("max_ratio", 1.5),
    # deploy-drill sentinels: the rush-hour deploy's TTFT tail may not
    # creep vs its own quiet arm across rounds, and a warm migration's
    # wire cost per session must stay near the quantized budget (a 1.5x
    # jump means someone fell back to a fatter rung / bf16 payloads);
    # drill.zero_drops / drill.bit_identical / swap.parity_ok ride the
    # unconditional must_stay_true block below
    "drill.ttft_p999_ratio": ("max_ratio", 2.0),
    "migrate.wire_bytes_per_session": ("max_ratio", 1.5),
}

# units where a larger headline value is worse
_LOWER_IS_BETTER = re.compile(r"\bms\b|latency|violations", re.I)


def load_rounds(root: str) -> List[Tuple[int, str, Dict[str, Any]]]:
    """All BENCH_r*.json under ``root`` as (round, path, doc), sorted by
    round number."""
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except Exception:
            continue
        out.append((int(m.group(1)), path, doc))
    out.sort(key=lambda t: t[0])
    return out


def _is_lower_better(parsed: Dict[str, Any]) -> bool:
    return bool(_LOWER_IS_BETTER.search(str(parsed.get("unit", ""))))


def diff_reports(old: Dict[str, Any], new: Dict[str, Any],
                 thresholds: Optional[Dict[str, Tuple[str, float]]] = None,
                 strict: bool = False) -> Dict[str, Any]:
    """Compare two ``parsed`` bench payloads. Returns
    ``{"comparable", "checks": [...], "violations": [...], "ok"}``.

    Metric identity = (metric, unit): when they differ the rounds are
    incomparable (ok unless ``strict``) — apples-to-apples only."""
    th = dict(DEFAULT_THRESHOLDS)
    th.update(thresholds or {})
    checks: List[Dict[str, Any]] = []
    violations: List[Dict[str, Any]] = []

    def check(name: str, rule: str, limit: float, old_v, new_v,
              observed: float, ok: bool) -> None:
        row = {"metric": name, "rule": rule, "limit": limit,
               "old": old_v, "new": new_v,
               "observed": round(observed, 4), "ok": ok}
        checks.append(row)
        if not ok:
            violations.append(row)

    same = (old.get("metric") == new.get("metric")
            and old.get("unit") == new.get("unit"))
    loosen = 0.9 if (new.get("contended") or old.get("contended")) else 1.0

    if same:
        ov, nv = old.get("value"), new.get("value")
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)) \
                and ov > 0:
            ratio = nv / ov
            if _is_lower_better(new):
                rule, limit = th["value_lower"]
                check("value", rule, limit / loosen, ov, nv, ratio,
                      ratio <= limit / loosen)
            else:
                rule, limit = th["value_higher"]
                check("value", rule, limit * loosen, ov, nv, ratio,
                      ratio >= limit * loosen)
        for key in ("mfu", "engine_mfu"):
            ov, nv = old.get(key), new.get(key)
            if isinstance(ov, (int, float)) and \
                    isinstance(nv, (int, float)) and ov > 0:
                rule, limit = th[key]
                ratio = nv / ov
                check(key, rule, limit * loosen, ov, nv, ratio,
                      ratio >= limit * loosen)
        ov, nv = old.get("hidden_comm_frac"), new.get("hidden_comm_frac")
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)):
            rule, limit = th["hidden_comm_frac"]
            drop = ov - nv
            check("hidden_comm_frac", rule, limit, ov, nv, drop,
                  drop <= limit)
        ov, nv = old.get("host_gap_ms"), new.get("host_gap_ms")
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)) \
                and ov > 0:
            rule, limit = th["host_gap_ms"]
            ratio = nv / ov
            check("host_gap_ms", rule, limit, ov, nv, ratio,
                  ratio <= limit)
        # serving-quant sentinels (serve_quant payloads): handoff wire
        # compression, wire SNR, and per-arm concurrent-session capacity
        ov, nv = old.get("handoff_wire_frac"), new.get("handoff_wire_frac")
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)) \
                and ov > 0:
            rule, limit = th["handoff_wire_frac"]
            ratio = nv / ov
            check("handoff_wire_frac", rule, limit, ov, nv, ratio,
                  ratio <= limit)
        ov = old.get("handoff_wire_snr_db")
        nv = new.get("handoff_wire_snr_db")
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)):
            rule, limit = th["handoff_wire_snr_db"]
            drop = ov - nv
            check("handoff_wire_snr_db", rule, limit, ov, nv, drop,
                  drop <= limit)
        # cross-process fleet sentinels (serve_procs payloads): KV wire
        # compression and the chaos arm's p99.9 failover tail
        for key in ("kv_wire_ratio", "ttft_p999_ms",
                    "chaos.ttft_p999_ratio"):
            ov, nv = old.get(key), new.get(key)
            if isinstance(ov, (int, float)) and \
                    isinstance(nv, (int, float)) and ov > 0:
                rule, limit = th[key]
                ratio = nv / ov
                check(key, rule, limit, ov, nv, ratio, ratio <= limit)
        # observability-plane sentinels (obs_fleet payloads): tracer
        # overhead trend and the worst clock-offset error
        ov = old.get("obs.trace_overhead_us")
        nv = new.get("obs.trace_overhead_us")
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)) \
                and ov > 0:
            rule, limit = th["obs.trace_overhead_us"]
            ratio = nv / ov
            check("obs.trace_overhead_us", rule, limit, ov, nv, ratio,
                  ratio <= limit)
        ov = old.get("obs.offset_err_ms")
        nv = new.get("obs.offset_err_ms")
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)):
            rule, limit = th["obs.offset_err_ms"]
            rise = nv - ov
            check("obs.offset_err_ms", rule, limit, ov, nv, rise,
                  rise <= limit)
        # fleet black-box sentinels (replay_fleet payloads): journal
        # append overhead and bytes-per-request trends
        for key in ("replay.journal_overhead_us",
                    "replay.journal_bytes_per_request"):
            ov, nv = old.get(key), new.get(key)
            if isinstance(ov, (int, float)) and \
                    isinstance(nv, (int, float)) and ov > 0:
                rule, limit = th[key]
                ratio = nv / ov
                check(key, rule, limit, ov, nv, ratio, ratio <= limit)
        # zero-downtime deploy sentinels (deploy_drill payloads): the
        # deploy-vs-quiet TTFT tail and the warm-migration wire cost
        for key in ("drill.ttft_p999_ratio",
                    "migrate.wire_bytes_per_session"):
            ov, nv = old.get(key), new.get(key)
            if isinstance(ov, (int, float)) and \
                    isinstance(nv, (int, float)) and ov > 0:
                rule, limit = th[key]
                ratio = nv / ov
                check(key, rule, limit, ov, nv, ratio, ratio <= limit)
        # tiered-KV sentinels (serve_tier payloads): host-tier session
        # capacity, warm-resume TTFT trend, and drafter accept rate
        for key in ("tier.sessions_per_gb", "spec.accept_rate"):
            ov, nv = old.get(key), new.get(key)
            if isinstance(ov, (int, float)) and \
                    isinstance(nv, (int, float)) and ov > 0:
                rule, limit = th[key]
                ratio = nv / ov
                check(key, rule, limit * loosen, ov, nv, ratio,
                      ratio >= limit * loosen)
        ov = old.get("tier.warm_resume_ttft_ratio")
        nv = new.get("tier.warm_resume_ttft_ratio")
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)) \
                and ov > 0:
            rule, limit = th["tier.warm_resume_ttft_ratio"]
            ratio = nv / ov
            check("tier.warm_resume_ttft_ratio", rule, limit, ov, nv,
                  ratio, ratio <= limit)
        for arm in ("bf16", "int8", "int4"):
            o_arm = old.get(arm) if isinstance(old.get(arm), dict) else {}
            n_arm = new.get(arm) if isinstance(new.get(arm), dict) else {}
            ov = o_arm.get("peak_concurrent_sessions")
            nv = n_arm.get("peak_concurrent_sessions")
            if isinstance(ov, (int, float)) and \
                    isinstance(nv, (int, float)) and ov > 0:
                rule, limit = th["sessions_capacity"]
                ratio = nv / ov
                check(f"{arm}.peak_concurrent_sessions", rule,
                      limit * loosen, ov, nv, ratio,
                      ratio >= limit * loosen)

    # chaos + observability certificates ride any payload that carries
    # them — the new round's flags must be true regardless of
    # comparability (a chaos round that dropped a request, or an obs
    # round whose clock estimate escaped its own uncertainty bound, is
    # broken on its own, not relative to the old round)
    for cert in ("chaos.zero_drops", "chaos.bit_identical",
                 "obs.trace_overhead_ok", "obs.offset_bound_ok",
                 "replay.bit_identical",
                 # a deploy that dropped or mutated a stream, or a
                 # rollout that rejoined a parity-failing replica, is
                 # broken on its own, not relative to the old round
                 "drill.zero_drops", "drill.bit_identical",
                 "swap.parity_ok", "swap.abort_ok"):
        if cert in new:
            check(cert, "must_stay_true", 1, old.get(cert),
                  new.get(cert), float(bool(new[cert])), bool(new[cert]))

    # quant acceptance gates ride every payload that carries them —
    # comparable or not, a failing gate in the NEW round always fails
    if "ok" in new and "violations" in new:
        n_viol = len(new.get("violations") or [])
        check("quant_gates", "must_pass", 0, None,
              new.get("value"), float(n_viol), bool(new["ok"]))
        old_viol = len(old.get("violations") or []) if "ok" in old else 0
        if "ok" in old:
            check("quant_violations", "no_growth", old_viol, old_viol,
                  n_viol, float(n_viol), n_viol <= old_viol)

    if not same and not checks:
        ok = not strict
        return {"comparable": False, "ok": ok, "checks": [],
                "violations": ([] if ok else [{
                    "metric": "metric_identity", "rule": "strict",
                    "old": f"{old.get('metric')} [{old.get('unit')}]",
                    "new": f"{new.get('metric')} [{new.get('unit')}]",
                    "ok": False}]),
                "note": "headline metric/unit changed between rounds"}
    return {"comparable": same, "ok": not violations, "checks": checks,
            "violations": violations}


def diff_markdown(result: Dict[str, Any], old_label: str,
                  new_label: str) -> str:
    lines = [f"### bench diff — {old_label} → {new_label}", ""]
    if not result.get("checks"):
        note = result.get("note", "no shared metrics")
        lines.append(f"(incomparable: {note}) — "
                     + ("FAIL (--strict)" if not result["ok"] else "pass"))
        return "\n".join(lines)
    lines += ["| metric | old | new | observed | rule | limit | pass |",
              "|---|---|---|---|---|---|---|"]
    for c in result["checks"]:
        lines.append(
            f"| {c['metric']} | {c['old']} | {c['new']} | "
            f"{c['observed']} | {c['rule']} | {c['limit']} | "
            f"{'PASS' if c['ok'] else 'FAIL'} |")
    lines.append("")
    lines.append("ok" if result["ok"] else
                 f"{len(result['violations'])} violation(s) — "
                 "exit nonzero")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_diff",
        description="compare the newest BENCH_r*.json against the "
                    "previous round; exit nonzero on regression")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--old", default=None,
                    help="explicit old artifact (default: second-newest "
                         "round)")
    ap.add_argument("--new", default=None,
                    help="explicit new artifact (default: newest round)")
    ap.add_argument("--strict", action="store_true",
                    help="incomparable rounds (headline changed shape) "
                         "fail instead of passing")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.old and args.new:
        pairs = []
        for p in (args.old, args.new):
            with open(p) as f:
                pairs.append((p, json.load(f)))
        (old_path, old_doc), (new_path, new_doc) = pairs
    else:
        rounds = load_rounds(args.root)
        if len(rounds) < 2:
            found = (f"no BENCH_r*.json under {args.root}" if not rounds
                     else f"only {os.path.basename(rounds[0][1])} found")
            print(json.dumps({"schema": SCHEMA, "ok": True,
                              "note": f"{found}: nothing to diff"}))
            return 0
        (_, old_path, old_doc), (_, new_path, new_doc) = rounds[-2:]

    result = diff_reports(old_doc.get("parsed") or {},
                          new_doc.get("parsed") or {},
                          strict=args.strict)
    result["schema"] = SCHEMA
    result["old"] = os.path.basename(old_path)
    result["new"] = os.path.basename(new_path)
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        print(diff_markdown(result, result["old"], result["new"]))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
