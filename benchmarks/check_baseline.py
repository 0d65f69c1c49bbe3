"""Gate a ``bench_engine`` JSON report against a committed baseline.

CI runs ``python -m benchmarks.bench_engine --smoke --out bench_smoke.json``
on every PR and then::

  python benchmarks/check_baseline.py bench_smoke.json \\
      benchmarks/baselines/bench_smoke.json

Two classes of checks, because CI runners make wall-clock noisy but the
hardware model is deterministic:

* **exact/deterministic** — simulator consistency must hold; crossbar,
  area-efficiency and energy numbers must match the baseline to a tight
  relative tolerance (they depend only on seeds and the pricing code, so
  any drift is a real behaviour change); the engine-vs-dense output
  difference must stay within the fp32 bound; and the quantized top-1
  agreement may not fall below the baseline by more than ``--top1-slack``.
* **throughput** — the engine-vs-dense wall-clock ratio (a *ratio*, so
  machine speed cancels) may not regress beyond ``--time-tol`` times the
  baseline ratio.

The ``verify`` entry is gated absolutely (no baseline needed): the
static verifier must report zero errors on the bench-compiled programs
and cost less than ``VERIFY_OVERHEAD_CEIL`` of compile time — a ratio,
so machine speed cancels.

The ``ranges`` entry is gated absolutely too: the range-certification
pass must report zero errors on both bench precisions, produce
byte-identical certificates across independent analyses of the same
program, and cost less than ``RANGES_OVERHEAD_CEIL`` times compile
time (it touches every stored weight, so its floor — unlike the
metadata-only verifier's — is comparable to compile's packing work).
Warnings are not gated — the deep VGG legitimately trips the V504
fp32-range warning through the channel-norm eps division.

The ``mapping`` entry gates the design-space search the same two ways:
the Pareto guarantee (searched never worse than the fixed paper scheme
on area *and* energy, at least one model strictly improved), the
zero-drift cost-model contract, search determinism, and the chosen
area/energy ratios are all deterministic; only the search-time-over-
compile-time ratio is wall-clock (gated loosely vs the baseline).

The ``service`` entry is gated the same two ways: its scheduling is
deterministic (fixed arrival trace -> exact ``batches_run`` /
``occupancy_mean``, ``trace_count`` must be exactly 1, skip statistics
must match the one-shot forward), while its wall-clock only enters
through the loose ``overhead_vs_forward`` ratio.

The ``http_service`` entry is gated absolutely (socket timing makes its
scheduling nondeterministic, so there is no baseline row): every request
served ok, the forward traced exactly once under socket-driven
concurrency, mean slot occupancy >= ``HTTP_OCCUPANCY_FLOOR`` through
the HTTP path, and the shed phase conserving requests (served + shed ==
submitted, at least one but not all shed, nothing admitted dropped).

With ``--trace FILE`` the Chrome trace-event artifact written by
``bench_engine --trace-out`` is validated too: it must parse, every
event must carry the trace-event schema fields (``ph``/``ts``/``pid``/
``tid``/``name``, ``dur`` on complete spans), and it must contain
compile-phase spans, the service's step spans with their four phases,
the begin/end async events of all 100 bursty-trace request lifecycles,
and one admit instant per lifecycle.  ``--require-mid-decode`` additionally demands
``admit_mid_decode`` instants — the CI serving-smoke job runs
``examples/serve_http.py --backend generate --trace-out`` and validates
that artifact here with the report arguments omitted (trace-only mode).
Span *durations* are wall-clock and never gated — only the artifact's
shape is.

Exit code 0 when everything holds; 1 with a per-check report otherwise.
Regenerate the baseline with the same ``--smoke`` run when an intentional
change shifts the deterministic numbers.
"""

from __future__ import annotations

import argparse
import json
import sys

# CI runners are noisy; a throughput regression has to be gross to fail.
DEFAULT_TIME_TOL = 3.0
# deterministic hardware-model numbers: effectively equality
DETERMINISTIC_RTOL = 1e-6
# top-1 agreement may wiggle by a boundary flip or two across platforms
DEFAULT_TOP1_SLACK = 0.02
MAX_ABS_DIFF_CEIL = 1e-2  # engine vs dense fp32 logits
# the static verifier must stay cheap enough to leave on at every trust
# boundary: < 10% of compile time on the bench mini network (an absolute
# ratio gate — machine speed cancels, so no baseline entry is needed)
VERIFY_OVERHEAD_CEIL = 0.10
# the range-certification pass touches every stored weight (interval
# transfer + cell-budget table, ~4 full passes), so unlike the
# metadata-only verifier its floor is comparable to compile's own
# packing work (~0.8x measured).  The gate keeps it from regressing
# past compile itself: < 1.5x compile time, same absolute ratio gate
RANGES_OVERHEAD_CEIL = 1.5
# the HTTP front end must keep the batch nearly full under the bursty
# trace (an absolute gate — no baseline entry needed): continuous
# batching is the point, so a mostly-idle batch is a regression even if
# every request is served correctly
HTTP_OCCUPANCY_FLOOR = 0.90

DETERMINISTIC_HW_FIELDS = (
    "crossbars",
    "naive_crossbars",
    "area_efficiency",
    "energy_pj",
    "index_kb",
)
DETERMINISTIC_QUANT_FIELDS = (
    "crossbars",
    "cells_per_weight",
    "weight_bytes",
    "area_win_vs_fp32",
    "energy_win_vs_fp32",
)


def _levels(report: dict) -> dict:
    out = {}
    for net in report.get("networks", []):
        for lv in net.get("levels", []):
            out[(net["network"], round(lv["sparsity"], 4))] = lv
    return out


class Checker:
    def __init__(self):
        self.failures: list[str] = []
        self.passed = 0

    def check(self, ok: bool, msg: str):
        if ok:
            self.passed += 1
        else:
            self.failures.append(msg)

    def close(self, cur: float, base: float, what: str):
        ok = abs(cur - base) <= DETERMINISTIC_RTOL * max(abs(base), 1e-12)
        self.check(ok, f"{what}: {cur!r} != baseline {base!r}")


def _check_level(c: Checker, tag, lv, blv, time_tol, top1_slack):
    hw, bhw = lv["hardware_report"], blv["hardware_report"]

    # throughput: ratio-vs-ratio, generous tolerance
    ratio, base_ratio = lv["engine_vs_dense"], blv["engine_vs_dense"]
    msg = (
        f"{tag}: engine-vs-dense throughput regressed "
        f"{ratio:.2f} > {time_tol} x baseline {base_ratio:.2f}"
    )
    c.check(ratio <= base_ratio * time_tol, msg)

    # numerics: engine must stay near the dense reference
    msg = (
        f"{tag}: engine-vs-dense max_abs_diff {lv['max_abs_diff']:.2e} "
        f"exceeds {MAX_ABS_DIFF_CEIL:.0e}"
    )
    c.check(lv["max_abs_diff"] <= MAX_ABS_DIFF_CEIL, msg)

    # deterministic hardware-model numbers
    for field in DETERMINISTIC_HW_FIELDS:
        c.close(hw[field], bhw[field], f"{tag}: {field}")
    c.close(lv["weight_bytes"], blv["weight_bytes"], f"{tag}: weight_bytes")

    q, bq = lv.get("quantized"), blv.get("quantized")
    c.check(q is not None, f"{tag}: quantized entry missing")
    if q and bq:
        agree, base_agree = (
            q["top1_agreement_vs_fp32"],
            bq["top1_agreement_vs_fp32"],
        )
        msg = (
            f"{tag}: quantized top-1 agreement {agree:.3f} fell more "
            f"than {top1_slack} below baseline {base_agree:.3f}"
        )
        c.check(agree >= base_agree - top1_slack, msg)
        for field in DETERMINISTIC_QUANT_FIELDS:
            c.close(q[field], bq[field], f"{tag}: quantized {field}")


def compare(current, baseline, time_tol, top1_slack) -> Checker:
    c = Checker()

    cons = current.get("consistency", {})
    msg = f"simulator consistency broken: {cons}"
    c.check(cons.get("per_layer_match") is True, msg)

    cur_levels, base_levels = _levels(current), _levels(baseline)
    missing = sorted(set(base_levels) - set(cur_levels))
    c.check(not missing, f"missing bench levels: {missing}")

    for key in sorted(set(base_levels) & set(cur_levels)):
        tag = f"{key[0]} s={key[1]}"
        _check_level(c, tag, cur_levels[key], base_levels[key], time_tol, top1_slack)

    sv, bsv = current.get("service"), baseline.get("service")
    c.check(sv is not None, "service throughput entry missing")
    if sv:
        c.check(
            sv.get("trace_count") == 1,
            f"service traced the forward {sv.get('trace_count')} times "
            "(must be exactly 1: fixed batch shape)",
        )
        c.check(
            sv.get("stats_exact") is True,
            "service skip statistics diverged from the one-shot forward",
        )
        c.check(
            sv.get("batches_run", 0) > 0 and sv.get("requests_per_s", 0) > 0,
            f"service ran no batches: {sv}",
        )
    if sv and bsv:
        # the arrival trace is fixed, so scheduling is deterministic
        c.close(sv["batches_run"], bsv["batches_run"],
                "service: batches_run")
        c.close(sv["occupancy_mean"], bsv["occupancy_mean"],
                "service: occupancy_mean")
        # loose wall-clock gate: per-batch service overhead over the bare
        # forward is a ratio, so machine speed cancels
        ovh, bovh = sv["overhead_vs_forward"], bsv["overhead_vs_forward"]
        c.check(
            ovh <= bovh * time_tol,
            f"service overhead_vs_forward regressed "
            f"{ovh:.2f} > {time_tol} x baseline {bovh:.2f}",
        )

    hs = current.get("http_service")
    c.check(hs is not None, "http_service entry missing")
    if hs:
        # everything here is an absolute gate: socket timing makes the
        # HTTP batches_run nondeterministic, so unlike the in-process
        # service entry there is nothing to pin against the baseline
        c.check(
            hs.get("all_ok") is True,
            f"http_service: not every request served ok: {hs}",
        )
        c.check(
            hs.get("trace_count") == 1,
            f"http_service traced the forward {hs.get('trace_count')} "
            "times (must be exactly 1: fixed batch shape)",
        )
        occ = hs.get("occupancy_mean", 0.0)
        c.check(
            occ >= HTTP_OCCUPANCY_FLOOR,
            f"http_service occupancy {occ:.3f} below "
            f"{HTTP_OCCUPANCY_FLOOR} through the HTTP path",
        )
        c.check(
            hs.get("requests_per_s", 0) > 0
            and hs.get("first_result_p99_s", 0) > 0
            and hs.get("http_completed", 0) >= hs.get("requests", 1),
            f"http_service SLO metrics empty: {hs}",
        )
        shed = hs.get("shed") or {}
        c.check(
            shed.get("conservation_ok") is True,
            f"http_service shed phase lost or corrupted requests: {shed}",
        )
        c.check(
            shed.get("trace_count") == 1,
            f"http_service shed server traced "
            f"{shed.get('trace_count')} times",
        )
        # the exact shed count races the worker's drain speed; only its
        # bounds are deterministic (the burst exceeds queue + slots, so
        # at least one request must shed; all of them may not)
        c.check(
            0 < shed.get("shed", 0) < shed.get("requests", 0),
            f"http_service shed count {shed.get('shed')} outside "
            f"(0, {shed.get('requests')})",
        )

    vf = current.get("verify")
    c.check(vf is not None, "verify overhead entry missing")
    if vf:
        c.check(
            vf.get("errors", 1) == 0,
            f"static verifier found {vf.get('errors')} error(s) in the "
            "bench-compiled program",
        )
        frac = vf.get("overhead_frac", 1.0)
        c.check(
            frac <= VERIFY_OVERHEAD_CEIL,
            f"verify overhead {frac:.1%} of compile time exceeds "
            f"{VERIFY_OVERHEAD_CEIL:.0%} "
            f"(compile {vf.get('compile_s', 0):.3f}s, "
            f"verify {vf.get('verify_s', 0):.3f}s)",
        )

    rg = current.get("ranges")
    c.check(rg is not None, "ranges overhead entry missing")
    if rg:
        c.check(
            rg.get("errors", 1) == 0,
            f"range certification found {rg.get('errors')} error(s) in "
            "the bench-compiled programs",
        )
        c.check(
            rg.get("deterministic") is True,
            "range certificates differ across analyses of the same "
            "program",
        )
        frac = rg.get("overhead_frac", 1.0)
        c.check(
            frac <= RANGES_OVERHEAD_CEIL,
            f"ranges overhead {frac:.2f}x compile time exceeds "
            f"{RANGES_OVERHEAD_CEIL:.1f}x "
            f"(compile {rg.get('compile_s', 0):.3f}s, "
            f"ranges {rg.get('ranges_s', 0):.3f}s)",
        )

    sh = current.get("sharded", {})
    if "max_abs_diff" in sh:
        msg = (
            f"sharded max_abs_diff {sh['max_abs_diff']:.2e} "
            f"exceeds {MAX_ABS_DIFF_CEIL:.0e}"
        )
        c.check(sh["max_abs_diff"] <= MAX_ABS_DIFF_CEIL, msg)

    mp = current.get("mapping")
    c.check(mp is not None, "mapping search entry missing")
    if mp:
        # Pareto guarantee: the searched mapping may never lose to the
        # fixed paper scheme on crossbar area or energy, and at least one
        # bench model must come out strictly ahead
        c.check(
            mp.get("all_searched_le_fixed") is True,
            "mapping: searched scheme worse than fixed on area or energy",
        )
        c.check(
            mp.get("any_strictly_improved") is True,
            "mapping: no bench model strictly improved by the search",
        )
        # zero-drift contract: mapping_cost must re-price every chosen
        # layer to the exact hardware_report numbers
        c.check(
            mp.get("cost_model_exact") is True,
            "mapping: cost model drifted from simulator pricing",
        )
        c.check(
            mp.get("search_deterministic") is True,
            "mapping: standalone re-search diverged from compiled choice",
        )
    bmp = baseline.get("mapping")
    if mp and bmp:
        cur_models = {m["model"]: m for m in mp.get("models", [])}
        for bm in bmp.get("models", []):
            m = cur_models.get(bm["model"])
            c.check(
                m is not None,
                f"mapping: model {bm['model']} missing from report",
            )
            if m is None:
                continue
            tag = f"mapping {bm['model']}"
            # ratios depend only on seeds and the pricing code
            c.close(m["area_ratio"], bm["area_ratio"], f"{tag}: area_ratio")
            c.close(m["energy_ratio"], bm["energy_ratio"],
                    f"{tag}: energy_ratio")
            c.close(m["searched"]["area_cells"], bm["searched"]["area_cells"],
                    f"{tag}: searched area_cells")
            c.close(m["evaluations"], bm["evaluations"],
                    f"{tag}: evaluations")
            # loose wall-clock gate: search time over a fixed compile is a
            # ratio, so machine speed cancels
            ovh, bovh = m["search_overhead"], bm["search_overhead"]
            c.check(
                ovh <= bovh * time_tol,
                f"{tag}: search overhead regressed "
                f"{ovh:.1f} > {time_tol} x baseline {bovh:.1f}",
            )
    return c


# the smoke service entry drains the fixed 100-request bursty trace, so
# the artifact must carry at least that many request lifecycles
MIN_REQUEST_SPANS = 100
# InferenceService.step: one span per batch and one per phase inside it
SERVICE_STEP_SPANS = (
    "service.step", "service.refill", "service.dispatch", "service.wait",
    "service.complete",
)


def check_trace(c: Checker, path: str,
                require_mid_decode: bool = False) -> None:
    """Validate the shape of a ``--trace-out`` Chrome trace artifact.

    With ``require_mid_decode`` the artifact must additionally carry at
    least one ``admit_mid_decode`` instant — a slot refilled while other
    slots were live between decode steps — with well-formed ``slot``/
    ``pos`` args (the per-slot continuous-batching property, produced by
    a generation serving run such as ``examples/serve_http.py --backend
    generate --trace-out``).
    """
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        c.check(False, f"trace: {path} unreadable: {e}")
        return
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        events = []
    c.check(bool(events), f"trace: traceEvents missing or empty in {path}")
    bad = []
    for e in events:
        ok = all(k in e for k in ("ph", "ts", "pid", "tid", "name"))
        if ok and e["ph"] == "X":
            ok = e.get("dur", -1) >= 0
        if not ok:
            bad.append(e)
    c.check(
        not bad,
        f"trace: {len(bad)} events missing schema fields, first: {bad[:1]}",
    )
    spans = [e for e in events if e["ph"] == "X"]
    if require_mid_decode:
        # a generation serving trace: decode/prefill step spans instead
        # of the bench trace's compile + classification step spans
        decode_spans = [
            e for e in spans
            if e.get("cat") == "serve" and e["name"] == "serve.decode"
        ]
        c.check(
            bool(decode_spans),
            "trace: no decode-step spans (ph=X, cat=serve, serve.decode)",
        )
    else:
        compile_spans = [e for e in spans if e.get("cat") == "compile"]
        c.check(
            bool(compile_spans),
            "trace: no compile-phase spans (ph=X, cat=compile)",
        )
        serve_names = {e["name"] for e in spans if e.get("cat") == "serve"}
        missing = sorted(set(SERVICE_STEP_SPANS) - serve_names)
        c.check(
            not missing,
            f"trace: no service step spans {missing} (ph=X, cat=serve)",
        )
    begins = [e for e in events if e["ph"] == "b" and e.get("cat") == "request"]
    ends = [e for e in events if e["ph"] == "e" and e.get("cat") == "request"]
    c.check(
        len(begins) >= MIN_REQUEST_SPANS,
        f"trace: only {len(begins)} request-lifecycle begin events "
        f"(need >= {MIN_REQUEST_SPANS})",
    )
    c.check(
        len(ends) == len(begins),
        f"trace: {len(begins)} request begins vs {len(ends)} ends",
    )
    admits = [
        e for e in events
        if e["ph"] == "n" and e.get("cat") == "request"
        and (e.get("args") or {}).get("event")
        in ("admit", "admit_mid_decode")
    ]
    c.check(
        len(admits) >= len(begins),
        f"trace: {len(admits)} admit instants for {len(begins)} request "
        "lifecycles (every admitted request must carry one)",
    )
    if require_mid_decode:
        mid = [
            e for e in admits
            if e["args"]["event"] == "admit_mid_decode"
        ]
        c.check(
            bool(mid),
            "trace: no admit_mid_decode instants — no slot was refilled "
            "while other slots were mid-decode",
        )
        bad = [
            e for e in mid
            if not (e["args"].get("slot", -1) >= 0
                    and e["args"].get("pos", 0) >= 1)
        ]
        c.check(
            not bad,
            f"trace: {len(bad)} admit_mid_decode instants with malformed "
            f"slot/pos args, first: {bad[:1]}",
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("current", nargs="?", default=None,
                    help="fresh bench_engine JSON (omit for --trace-only "
                         "validation)")
    ap.add_argument("baseline", nargs="?", default=None,
                    help="committed baseline JSON")
    ap.add_argument(
        "--time-tol",
        type=float,
        default=DEFAULT_TIME_TOL,
        help="allowed engine-vs-dense ratio blow-up",
    )
    ap.add_argument(
        "--top1-slack",
        type=float,
        default=DEFAULT_TOP1_SLACK,
        help="allowed quantized top-1 agreement drop",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="FILE",
        help="also validate a bench_engine --trace-out Chrome trace artifact",
    )
    ap.add_argument(
        "--require-mid-decode",
        action="store_true",
        help="the --trace artifact must carry admit_mid_decode instants "
             "(a generation serving trace)",
    )
    args = ap.parse_args(argv)
    if (args.current is None) != (args.baseline is None):
        ap.error("current and baseline must be given together")
    if args.current is None and not args.trace:
        ap.error("nothing to check: give current+baseline and/or --trace")

    if args.current is not None:
        with open(args.current) as f:
            current = json.load(f)
        with open(args.baseline) as f:
            baseline = json.load(f)
        c = compare(current, baseline, args.time_tol, args.top1_slack)
    else:
        c = Checker()
    if args.trace:
        check_trace(c, args.trace,
                    require_mid_decode=args.require_mid_decode)
    print(f"{c.passed} checks passed, {len(c.failures)} failed")
    for msg in c.failures:
        print(f"FAIL: {msg}")
    return 1 if c.failures else 0


if __name__ == "__main__":
    sys.exit(main())
