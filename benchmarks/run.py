"""Benchmark driver — one section per paper table/claim.

  bench_paper    — fig. 5(a)/(b) + solver-time claims (§4.2)
  bench_fleet    — fleet-runtime scenario × policy × scale sweep (repro.fleet)
  bench_roofline — §Roofline table from the dry-run artifacts

Default mode prints ``name,key=value,...`` CSV rows for every section.
``--json`` runs the fleet sweep (scale ×1 scenario × policy grid, the
×2/×4/×8 solver-scaling sweep with 400×scale windows, a ×32 planetary
slice under the hierarchical planner, ×64/×256 steady-tick rows with
a >100k-app window, the ×64/×256 admission fast-path microbench —
scalar vs vectorized arrival path with a ≥5× decision-phase gate — and
the serving strategy sweep: serving-fleet under each forced migration
strategy at ×1/×8 with a kv-ship-beats-replay gate, zero recomputed
tokens at no worse mean migration downtime) and
writes machine-readable rows to ``BENCH_fleet.json``.  ``--smoke`` runs a CI sanity slice (request
streams + adaptive policy, a backbone cut, the decomposed/incremental
planners at ``--scale`` — plus, at ``--scale`` ≥ 16, the hierarchical
planner with a fingerprint-parity gate and a steady-tick latency budget —
the elastic-bridge cells: simulated-vs-flat fingerprint parity plus
byte-derived phase timings on hetero-expansion, a scalar-vs-vector
admission-mode fingerprint-parity cell (plus, at ``--scale`` ≥ 16, the
admission fast-path microbench with its ≥5× decision-phase speedup and
arrival-throughput gates), an SLO burn-rate → policy-escalation cell, a calibration cell pair (drift detectors must
catch a 4×-miscalibrated size model, ``cost_feedback`` must collapse the
downtime prediction error without perturbing the behavior fingerprint),
a serving-fleet cell (a flash crowd lands mid-reconfiguration with
kv-ship forced: token conservation with zero cancellations, ≥1
completed kv-ship migration, a reported per-token p99),
and a traced run validated against the Chrome trace_event schema) and
exits non-zero on any failure.  ``--trace out.json`` runs one scenario
with the dual-clock span tracer attached and writes a Perfetto-loadable
trace (open in ui.perfetto.dev or chrome://tracing).  ``--report
calibration`` dumps the full calibration ledger — residual summaries,
drift records, and per-move decision provenance — for the
hetero-expansion acceptance pair.
"""

import argparse
import json
import sys
import traceback


def _ratio(v):
    from repro.fleet.obs.metrics import fmt_ratio  # late: needs PYTHONPATH=src
    return fmt_ratio(v)


def _traced_run(scenario: str, policy: str, seed: int, **scenario_kwargs):
    """One scenario run with the span tracer attached → (tracer, telemetry)."""
    from repro.fleet import SpanTracer, build_scenario, get_policy

    spec = build_scenario(scenario, seed=seed, **scenario_kwargs)
    tracer = SpanTracer()
    runtime = spec.make_runtime(get_policy(policy), tracer=tracer)
    tel = runtime.run(spec.event_queue(), scenario=scenario, seed=seed)
    return tracer, tel


def run_trace(out_path: str, scenario: str, policy: str, seed: int) -> int:
    from repro.fleet import validate_trace

    tracer, tel = _traced_run(scenario, policy, seed)
    n = tracer.write(out_path)
    problems = validate_trace(tracer.to_dict())
    print(f"wrote {out_path}: {n} trace events "
          f"({scenario}/{policy}, {len(tel.ticks)} ticks, "
          f"{tel.counters['migrations_completed']} migrations completed)")
    for p in problems:
        print(f"  INVALID: {p}")
    if not problems:
        print("  trace schema: OK — load in ui.perfetto.dev / chrome://tracing")
    return 1 if problems else 0


def run_json(out_path: str, seed: int) -> int:
    from benchmarks.bench_fleet import (
        DEFAULT_POLICIES,
        SCALE_SWEEP_POLICIES,
        SCALE_SWEEP_SCALES,
        admission_rows,
        calibration_rows,
        planetary_rows,
        scale_sweep,
        serving_rows,
        steady_tick_rows,
        sweep,
    )

    rows = sweep(seed=seed)
    scaled = scale_sweep(seed=seed)
    # Planetary slice: the ×32 scenario sweep (hierarchical planner vs its
    # flat equivalent and the greedy floor) plus the ×32/×64/×256
    # steady-tick microbench with the >100k-app window.
    scaled += scale_sweep(scales=(32,), scenarios=("paper-steady-state",),
                          policies=("incremental", "hierarchical", "greedy"),
                          seed=seed, with_ticks=False)
    steady = steady_tick_rows(seed=seed)
    steady += steady_tick_rows((32,), seed=seed,
                               policies=("decomposed", "incremental",
                                         "hierarchical"))
    steady += planetary_rows(seed=seed)
    calib = calibration_rows(seed=seed)
    admission = admission_rows(seed=seed)
    serving = serving_rows(seed=seed)
    doc = {
        "benchmark": "fleet_runtime",
        "seed": seed,
        "policies": list(DEFAULT_POLICIES),
        "scale_sweep": {"scales": list(SCALE_SWEEP_SCALES) + [32],
                        "policies": list(SCALE_SWEEP_POLICIES)},
        "rows": rows + scaled,
        "steady_tick": steady,
        "calibration": calib,
        "admission": admission,
        "serving": serving,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {out_path}: {len(rows)} scale-1 rows + "
          f"{len(scaled)} scale-sweep rows + {len(steady)} steady-tick rows + "
          f"{len(calib)} calibration rows + {len(admission)} admission rows + "
          f"{len(serving)} serving rows")
    ok = 0
    # Serving acceptance: at each scale kv-ship must beat replay on
    # decode-heavy sessions — zero recomputed tokens (where replay must
    # show the recompute cost it pays) at no worse mean serving-migration
    # downtime.
    srv_by_scale = {}
    for r in serving:
        srv_by_scale.setdefault(r["scale"], {})[r["forced_strategy"]] = r
    for sc in sorted(srv_by_scale):
        cells = srv_by_scale[sc]
        for st in ("auto", "drain", "replay", "kv-ship"):
            r = cells.get(st)
            if r is None:
                continue
            s = r.get("serving") or {}
            dt = r["mean_serving_downtime_s"]
            print(f"  serving x{sc} {st:8s}: "
                  f"tok/s={s.get('tokens_per_s', 0):.2f} "
                  f"p99={s.get('p99_token_latency_s', 0):.4f}s "
                  f"rec={s.get('tokens_recomputed', 0):6d} "
                  f"cancel={s.get('tokens_cancelled', 0):4d} "
                  f"migs={r['serving_migrations_completed']:3d} "
                  f"mean_dt={dt if dt is not None else float('nan'):.3f}s")
        kv, rp = cells.get("kv-ship"), cells.get("replay")
        kv_s = (kv or {}).get("serving") or {}
        rp_s = (rp or {}).get("serving") or {}
        good = (kv is not None and rp is not None
                and kv_s.get("tokens_recomputed") == 0
                and rp_s.get("tokens_recomputed", 0) > 0
                and kv["mean_serving_downtime_s"] is not None
                and rp["mean_serving_downtime_s"] is not None
                and kv["mean_serving_downtime_s"]
                <= rp["mean_serving_downtime_s"])
        print(f"  serving x{sc}: kv-ship rec==0 & replay rec>0 & "
              f"kv downtime <= replay [{'OK' if good else 'MISS'}]")
        ok |= 0 if good else 1
    # Admission fast-path acceptance: the vectorized decision phase must
    # beat the scalar reference ≥5× at p50 on the planetary cells (the
    # rows assert scalar↔vector placement parity internally; end-to-end
    # p50/p99 ride along as evidence columns).
    for r in admission:
        good = r["decide_speedup_p50"] >= 5.0
        print(f"  admission x{r['scale']}: {r['arrivals']} arrivals, "
              f"place p50 {r['p50_place_scalar_s'] * 1e6:.1f}us -> "
              f"{r['p50_place_s'] * 1e6:.1f}us "
              f"({r['place_speedup_p50']:.1f}x e2e), decide p50 "
              f"{r['decide_p50_scalar_s'] * 1e6:.1f}us -> "
              f"{r['decide_p50_vector_s'] * 1e6:.1f}us "
              f"({r['decide_speedup_p50']:.1f}x) "
              f"[>=5x: {'OK' if good else 'MISS'}], "
              f"{r['arrivals_per_s']:.0f} arrivals/s")
        ok |= 0 if good else 1
    # Calibration acceptance (ISSUE): on hetero-expansion the p90 relative
    # error of predicted vs measured migration downtime must drop ≥5× with
    # the self-correcting cost model (`RuntimeConfig.cost_feedback`) on.
    c_off = next((r for r in calib if not r["cost_feedback"]), None)
    c_on = next((r for r in calib if r["cost_feedback"]), None)
    if c_off and c_on and c_off["p90_calib_downtime_err"] is not None \
            and c_on["p90_calib_downtime_err"] is not None:
        ratio = c_off["p90_calib_downtime_err"] / max(
            c_on["p90_calib_downtime_err"], 1e-9)
        good = ratio >= 5.0
        print(f"  calibration hetero-expansion: p90 downtime err "
              f"{c_off['p90_calib_downtime_err']:.4f} → "
              f"{c_on['p90_calib_downtime_err']:.4f} ({ratio:.1f}x) "
              f"[>=5x: {'OK' if good else 'MISS'}]")
        ok |= 0 if good else 1
    else:
        print("  calibration hetero-expansion pair missing p90 columns [MISS]")
        ok |= 1
    for sc in sorted({r["scale"] for r in steady}):
        by_pol = {r["policy"]: r for r in steady if r["scale"] == sc}
        cols = " ".join(
            f"{pol}={row['mean_steady_tick_s'] * 1e3:.1f}ms"
            for pol, row in by_pol.items())
        extra = ""
        if "decomposed" in by_pol and "incremental" in by_pol:
            inc = by_pol["incremental"]
            ratio = by_pol["decomposed"]["mean_steady_tick_s"] / max(
                inc["mean_steady_tick_s"], 1e-9)
            extra = (f" ({ratio:.1f}x, reused {inc['regions_reused_last']}/"
                     f"{inc['regions_reused_last'] + inc['regions_solved_last']})")
        if sc >= 32:
            # Planetary acceptance: steady ticks under 100 ms at ×32+.
            p50s = {pol: row["p50_steady_tick_s"]
                    for pol, row in by_pol.items()
                    if pol in ("incremental", "hierarchical")}
            good = p50s and all(v < 0.1 for v in p50s.values())
            extra += f"  [p50 < 100ms: {'OK' if good else 'MISS'}]"
            ok |= 0 if good else 1
        print(f"  steady-tick x{sc}: {cols}{extra}")
    # Incremental-vs-full acceptance: identical behavior fingerprints at
    # scale ×1 (deterministic policies), the hierarchical planner's
    # fingerprint parity wherever its flat equivalent ran, and the ×4
    # window-1600 sweep's planning-latency ratio.
    by_cell = {(r["scenario"], r["scale"], r["policy"]): r
               for r in rows + scaled}
    for r in rows + scaled:
        flag = ""
        if (r["scenario"] == "paper-steady-state" and r["policy"] == "milp"
                and r["scale"] == 1):
            # Paper fig. 5(b): moved-app mean X+Y ≈ 1.96.
            in_env = (r["mean_moved_ratio"] is not None
                      and abs(r["mean_moved_ratio"] - 1.96) <= 0.15)
            flag = f"  [paper envelope ±0.15: {'OK' if in_env else 'MISS'}]"
            ok |= 0 if in_env else 1
        if r["policy"] == "incremental":
            dec = by_cell.get((r["scenario"], r["scale"], "decomposed"))
            if dec is not None:
                if r["scale"] == 1:
                    same = r["fingerprint"] == dec["fingerprint"]
                    flag += f"  [fp == decomposed: {'OK' if same else 'MISS'}]"
                    ok |= 0 if same else 1
                elif dec["mean_solver_time_s"] > 0:
                    speedup = dec["mean_solver_time_s"] / max(
                        r["mean_solver_time_s"], 1e-9)
                    flag += f"  [vs decomposed: {speedup:.1f}x]"
        if r["policy"] == "hierarchical":
            inc = by_cell.get((r["scenario"], r["scale"], "incremental"))
            if inc is not None:
                same = r["fingerprint"] == inc["fingerprint"]
                flag += f"  [fp == incremental: {'OK' if same else 'MISS'}]"
                ok |= 0 if same else 1
        print(f"  {r['scenario']:28s} {r['policy']:11s} x{r['scale']:<2d} "
              f"ratio={_ratio(r['mean_moved_ratio'])} "
              f"ratio_w={_ratio(r['mean_moved_ratio_weighted'])} "
              f"moves={r['moves']:4d} "
              f"migs={r['migrations_completed']:3d}/{r['migrations_started']:3d} "
              f"abort={r['migrations_aborted']:2d} "
              f"solver_max={r['max_solver_time_s']:7.3f}s "
              f"gain={r['total_gain']:8.3f} wall={r['wall_s']:.2f}s{flag}")
    return ok


def run_smoke(seed: int, scale: int) -> int:
    from benchmarks.bench_fleet import smoke

    rows = smoke(seed=seed, scale=scale)
    bad = 0
    for r in rows:
        ok = r["admitted"] > 0 and r["ticks"] > 0
        if r["scenario"] == "backbone-cut":
            ok = ok and r["link_failures"] > 0
        if r["policy"] == "incremental":
            # Solver microbenchmark gate: the warm-start path must be live.
            ok = ok and r["warm_start_hits"] > 0
        if r["scenario"] == "hetero-expansion":
            # Elastic-bridge gate: declared-state jobs must execute real
            # snapshot → transfer → restore pipelines with byte-derived
            # phase times.
            ok = (ok and r["migrations_completed"] > 0
                  and r["total_snapshot_s"] > 0 and r["total_restore_s"] > 0)
        if r["policy"] == "adaptive" and r["scenario"] == "site-outage":
            # SLO observe→act gate: burn-rate breaches must fire AND pull
            # the adaptive ladder back toward the exact tier.
            ok = ok and r["slo_breaches"] > 0 and r["slo_escalations"] > 0
        bad |= 0 if ok else 1
        print(f"  {r['scenario']:28s} {r['policy']:11s} x{r['scale']:<2d} "
              f"backend={r['backend']:9s} "
              f"admitted={r['admitted']} ticks={r['ticks']} "
              f"migs={r['migrations_completed']} "
              f"ratio={_ratio(r['mean_moved_ratio'])} "
              f"warm={r['warm_start_hits']}/{r['regions_solved']} "
              f"reused={r['regions_reused']} "
              f"phases={r['total_snapshot_s']:.2f}/"
              f"{r['total_transfer_s']:.2f}/{r['total_restore_s']:.2f}s "
              f"slo={r['slo_breaches']}b/{r['slo_escalations']}e "
              f"[{'OK' if ok else 'FAIL'}]")
    if scale >= 16:
        # Hierarchical parity gate: above the 4000-node activation gate
        # the region-of-regions planner must still fingerprint identically
        # to the flat incremental planner on the same cell.
        pair = {r["policy"]: r["fingerprint"] for r in rows
                if r["scenario"] == "paper-steady-state"
                and r["scale"] == scale
                and r["policy"] in ("incremental", "hierarchical")}
        if len(pair) == 2:
            same = pair["hierarchical"] == pair["incremental"]
            print(f"  hierarchical parity x{scale} (fp == incremental): "
                  f"{'OK' if same else 'FAIL'}")
            bad |= 0 if same else 1
        else:
            print("  hierarchical parity pair missing from smoke rows [FAIL]")
            bad |= 1
        # Planetary steady-tick budget gate: quiet ticks at ×scale must
        # come in under the 100 ms acceptance ceiling.
        from benchmarks.bench_fleet import steady_tick_rows

        st = steady_tick_rows((scale,), seed=seed,
                              policies=("incremental", "hierarchical"))
        worst = max(r["p50_steady_tick_s"] for r in st)
        ok = worst < 0.1
        cols = " ".join(f"{r['policy']}={r['p50_steady_tick_s'] * 1e3:.1f}ms"
                        for r in st)
        print(f"  steady-tick budget x{scale}: {cols} p50<100ms "
              f"[{'OK' if ok else 'FAIL'}]")
        bad |= 0 if ok else 1
        # Admission fast-path gates at planetary scale: the vectorized
        # decision phase (the part the array ledger + decision cache
        # replace) must beat the retained scalar reference ≥5× at p50,
        # and end-to-end arrival throughput must clear the budget.  The
        # cell also asserts scalar↔vector placement parity internally.
        from benchmarks.bench_fleet import admission_rows

        ad = admission_rows(seed=seed, scales=(scale,),
                            decide_samples=2000)[0]
        dec_ok = ad["decide_speedup_p50"] >= 5.0
        thr_ok = ad["arrivals_per_s"] >= 10_000
        ok = dec_ok and thr_ok
        print(f"  admission fast path x{scale}: decide p50 "
              f"{ad['decide_p50_scalar_s'] * 1e6:.1f}us -> "
              f"{ad['decide_p50_vector_s'] * 1e6:.1f}us "
              f"({ad['decide_speedup_p50']:.1f}x) "
              f"[>=5x: {'OK' if dec_ok else 'FAIL'}], "
              f"{ad['arrivals_per_s']:.0f} arrivals/s "
              f"(scalar {ad['arrivals_per_s_scalar']:.0f}) "
              f"[>=10k/s: {'OK' if thr_ok else 'FAIL'}] "
              f"[{'OK' if ok else 'FAIL'}]")
        bad |= 0 if ok else 1
    # Elastic-bridge parity gate: the simulated backend's no-declared-state
    # fallback must be behavior-identical to the flat executor model.
    pair = {r["backend"]: r["fingerprint"] for r in rows
            if r["scenario"] == "site-outage" and r["policy"] == "greedy"}
    if len(pair) == 2:
        same = pair["simulated"] == pair["flat"]
        print(f"  bridge parity (site-outage simulated vs flat): "
              f"{'OK' if same else 'FAIL'}")
        bad |= 0 if same else 1
    else:
        print("  bridge parity pair missing from smoke rows [FAIL]")
        bad |= 1
    # Admission-mode parity gate: the vectorized admission fast path must
    # fingerprint bit-identically to the retained scalar reference loop on
    # the same scenario cell (pure mechanism, zero behavior drift).
    pair = {r["admission_mode"]: r["fingerprint"] for r in rows
            if r["scenario"] == "paper-steady-state"
            and r["policy"] == "greedy" and r["scale"] == 1}
    if len(pair) == 2:
        same = pair["vector"] == pair["scalar"]
        print(f"  admission parity (scalar vs vector fingerprint): "
              f"{'OK' if same else 'FAIL'}")
        bad |= 0 if same else 1
    else:
        print("  admission parity pair missing from smoke rows [FAIL]")
        bad |= 1
    # Calibration gates: on the node-outage pair (backend bytes 4× the
    # flat pricing belief) the ledger must flag the miscalibration
    # (drift detectors fire feedback-off), the backend-informed
    # predictions must shrink the p90 downtime error, and turning the
    # feedback knob must NOT perturb the behavior fingerprint.
    pair = {bool(r["cost_feedback"]): r for r in rows
            if r["scenario"] == "node-outage" and r["policy"] == "greedy"}
    if len(pair) == 2:
        off, on = pair[False], pair[True]
        drift_ok = off["calib_drifts"] > 0
        p_off, p_on = off["p90_calib_downtime_err"], on["p90_calib_downtime_err"]
        conv_ok = p_off is not None and p_on is not None and p_on < p_off
        fp_ok = off["fingerprint"] == on["fingerprint"]
        ok = drift_ok and conv_ok and fp_ok
        print(f"  calibration smoke (node-outage 4x bytes): "
              f"drifts={off['calib_drifts']} "
              f"p90_err={p_off}->{p_on} "
              f"drift fired: {'OK' if drift_ok else 'FAIL'}, "
              f"err shrank: {'OK' if conv_ok else 'FAIL'}, "
              f"fp unperturbed: {'OK' if fp_ok else 'FAIL'} "
              f"[{'OK' if ok else 'FAIL'}]")
        bad |= 0 if ok else 1
    else:
        print("  calibration smoke pair missing from smoke rows [FAIL]")
        bad |= 1
    # Serving gate: the flash-crowd serving-fleet cell forces kv-ship
    # fleet-wide; every submitted token must be decoded (conservation with
    # zero cancellations), at least one kv-ship migration must complete
    # mid-decode (echoed by the calibration ledger's per-strategy counts),
    # and the per-token p99 must be reported.
    srow = next((r for r in rows if r["scenario"] == "serving-fleet"), None)
    if srow is not None and srow.get("serving"):
        s = srow["serving"]
        conserve_ok = (s["tokens_decoded"] + s["tokens_cancelled"]
                       == s["tokens_submitted"])
        lossless_ok = s["tokens_cancelled"] == 0
        mig_ok = s["migrations"].get("kv-ship", 0) >= 1
        calib_ok = (srow.get("calib_strategies") or {}).get("kv-ship", 0) >= 1
        p99_ok = s["p99_token_latency_s"] > 0
        ok = conserve_ok and lossless_ok and mig_ok and calib_ok and p99_ok
        print(f"  serving smoke (serving-fleet kv-ship flash): "
              f"tokens={s['tokens_decoded']}/{s['tokens_submitted']} "
              f"cancel={s['tokens_cancelled']} "
              f"kv_migs={s['migrations'].get('kv-ship', 0)} "
              f"p99={s['p99_token_latency_s']:.4f}s "
              f"conserved: {'OK' if conserve_ok else 'FAIL'}, "
              f"lossless: {'OK' if lossless_ok else 'FAIL'}, "
              f"kv-ship completed: {'OK' if mig_ok else 'FAIL'}, "
              f"calib strategy counted: {'OK' if calib_ok else 'FAIL'} "
              f"[{'OK' if ok else 'FAIL'}]")
        bad |= 0 if ok else 1
    else:
        print("  serving smoke row missing serving summary [FAIL]")
        bad |= 1
    # Trace smoke: a traced run must export a schema-valid Chrome
    # trace_event document with ≥1 tick-phase span and ≥1 migration whose
    # snapshot/copy/restore phases nest inside it (validate_trace checks
    # all of this), bit-identical in fingerprint to the untraced run.
    from repro.fleet import validate_trace

    from repro.fleet import build_scenario, get_policy

    tracer, tel = _traced_run("site-outage", "incremental", seed,
                              n_arrivals=150)
    doc = tracer.to_dict()
    problems = validate_trace(doc)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    n_tick = sum(1 for e in spans if e["name"] == "tick")
    n_mig = sum(1 for e in spans if e["name"].startswith("migrate"))
    spec = build_scenario("site-outage", seed=seed, n_arrivals=150)
    plain = spec.make_runtime(get_policy("incremental")).run(
        spec.event_queue(), scenario="site-outage", seed=seed)
    neutral = tel.fingerprint() == plain.fingerprint()
    ok = not problems and n_tick > 0 and n_mig > 0 and neutral
    print(f"  trace smoke (site-outage/incremental): {len(spans)} spans, "
          f"{n_tick} ticks, {n_mig} migrations, "
          f"traced fp == untraced: {'OK' if neutral else 'FAIL'} "
          f"[{'OK' if ok else 'FAIL'}]")
    for p in problems:
        print(f"    INVALID: {p}")
    bad |= 0 if ok else 1
    return bad


def run_report(seed: int) -> int:
    """``--report calibration``: dump the full calibration ledger for the
    hetero-expansion acceptance pair — residual summaries, every
    `CalibrationDrift` record, and the per-move decision provenance
    (`MoveProvenance`) explaining *why* each committed move won."""
    from repro.fleet import MigrationCostModel, build_scenario, get_policy

    for feedback in (False, True):
        spec = build_scenario("hetero-expansion", seed=seed)
        spec.config.cost_feedback = feedback
        policy = (get_policy("greedy", cost_model=MigrationCostModel())
                  if feedback else get_policy("greedy"))
        runtime = spec.make_runtime(policy)
        tel = runtime.run(spec.event_queue(), scenario="hetero-expansion",
                          seed=seed)
        rep = tel.calibration
        hist = runtime.metrics.histogram("calibration/downtime_rel_err")
        print(f"# calibration report: hetero-expansion/greedy "
              f"cost_feedback={'on' if feedback else 'off'}")
        print(f"  joined={rep['samples']} excluded={rep['excluded']} "
              f"unmatched={rep['unmatched']} pending={rep['pending']} "
              f"learned_apps={rep['learned_apps']} "
              f"contention_s={rep['contention_s_total']:.3f}")
        print(f"  downtime_rel_err p50={hist.percentile(0.5):.4f} "
              f"p90={hist.percentile(0.9):.4f}")
        for dr in rep["drifts"]:
            print(f"  drift {json.dumps(dr, sort_keys=True)}")
        prov = rep["provenance"]
        print(f"  provenance: {prov['moves']} moves, "
              f"{prov['price_binding']} price-binding, "
              f"{prov['budget_binding']} budget-binding")
        for p in prov["records"]:
            print(f"  why {json.dumps(p, sort_keys=True)}")
    return 0


def run_csv(seed: int = 0) -> int:
    from benchmarks import bench_fleet, bench_paper, bench_roofline

    sections = [
        ("paper", bench_paper.run),
        ("fleet", lambda: bench_fleet.run(seed=seed)),
        ("roofline", bench_roofline.run),
    ]
    failed = 0
    for name, fn in sections:
        print(f"# === {name} ===")
        try:
            for row in fn():
                print(row)
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"{name},ERROR")
    return failed


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="run the fleet sweep and write BENCH_fleet.json")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI sanity slice of the fleet sweep")
    ap.add_argument("--out", default="BENCH_fleet.json",
                    help="output path for --json (default: BENCH_fleet.json)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=2,
                    help="topology scale for the --smoke planner cells "
                         "(≥16 adds the hierarchical parity + steady-tick "
                         "budget gates)")
    ap.add_argument("--report", choices=("calibration",),
                    help="dump one observability report (calibration: the "
                         "predicted-vs-actual ledger + decision provenance "
                         "for the hetero-expansion pair)")
    ap.add_argument("--trace", metavar="OUT",
                    help="run one traced scenario and write Chrome/Perfetto "
                         "trace_event JSON to OUT")
    ap.add_argument("--trace-scenario", default="site-outage",
                    help="scenario for --trace (default: site-outage)")
    ap.add_argument("--trace-policy", default="incremental",
                    help="policy for --trace (default: incremental)")
    args = ap.parse_args()
    if args.report:
        sys.exit(run_report(args.seed))
    if args.trace:
        sys.exit(run_trace(args.trace, args.trace_scenario,
                           args.trace_policy, args.seed))
    if args.smoke:
        sys.exit(run_smoke(args.seed, args.scale))
    sys.exit(run_json(args.out, args.seed) if args.json else run_csv(args.seed))


if __name__ == "__main__":
    main()
