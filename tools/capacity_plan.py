#!/usr/bin/env python
"""Serve capacity planner: analytic TTFT/p99 prediction + closed-loop
validation against the telemetry hub (ISSUE 18 tentpole, side 2).

Modes:

  python tools/capacity_plan.py                      # analytic report
  python tools/capacity_plan.py --validate           # closed loop (CPU)
  python tools/capacity_plan.py --self-check

**Report** (default): prices the serve loop from the STATIC cost models
alone — the HLO-evidence `serve_decode` roofline split into weight-read
floor + per-stream slope, prefill via the analyzer's per-op FLOPs
registry, hot-swap publish wire cost over the PR 16 DCN tier — and
sweeps offered load up to and past the saturation knee, printing
predicted p50/p99, utilization rho, and the M/G/k wait rail per rate.
No hardware, no serving, deterministic.

**Validate**: calibrates a DeviceProfile from the live CPU tiny-GPT
loop (static/capacity.calibrate_cpu), then for each builtin workload
spec (steady Poisson / diurnal wave / flash crowd) replays the SAME
deterministic schedule twice — once through the beat simulation
(prediction), once through the real ServeLoop via traffic/harness with
a TelemetryHub scoring the run from its merged histograms — and
asserts hub-observed throughput + TTFT/token p50 land within
FLAGS_capacity_p50_band_pct of prediction and the p99s within
FLAGS_capacity_p99_band_pct. The achieved headroom is written to
HLO_EVIDENCE.json `graphs.capacity_validation.band_headroom_x` and
gated >= 1.0 by framework_lint.check_perf_floors.

Flag/doc pins live in self_check (TOOL_CROSS_CHECKS).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS_DIR)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the bands and knobs this tool runs with; self_check pins them against
# core/flags.py defaults and the docs/traffic_lab.md flag table
CAPACITY_FLAG_DEFAULTS = {
    "FLAGS_capacity_p50_band_pct": 25.0,
    "FLAGS_capacity_p99_band_pct": 40.0,
    "FLAGS_capacity_knee_rho": 0.85,
    "FLAGS_capacity_calib_beats": 32,
}
TRAFFIC_FLAG_DEFAULTS = {
    "PADDLE_TRAFFIC_SEED": 0,
    "PADDLE_TRAFFIC_TIME_SCALE": 1.0,
    "PADDLE_TRAFFIC_CLIENTS": 4,
}

# the validation operating point: builtin specs at this rate/duration
# against the harness's default tiny serve shape (build_tiny_loop)
VALIDATE_SPECS = ("steady", "diurnal", "flash")
VALIDATE_RATE = 40.0
VALIDATE_DURATION_S = 10.0
VALIDATE_SEED = 7
VALIDATE_SERVE = {"max_active": 8, "kv_blocks": 48, "block_size": 8,
                  "max_seq_len": 48}

_HEADROOM_CAP = 99.0


def _load_evidence(path):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# report (analytic, no hardware)
# ---------------------------------------------------------------------------

def report(evidence_path, device="tpu-v3", rate=None, duration_s=4.0,
           seed=None):
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.static import capacity as C
    from paddle_tpu.traffic import workload as W

    if seed is None:
        seed = int(_flags.flag("PADDLE_TRAFFIC_SEED"))
    ev = _load_evidence(evidence_path)
    prof = C.analytic_profile(ev, device=device)
    probe = W.builtin_spec("steady", rate=rate or 100.0,
                           duration_s=duration_s)
    events = W.schedule(probe, seed)
    import numpy as np
    mean_new = float(np.mean([e.new_tokens for e in events]))
    mean_prompt = float(np.mean([e.prompt.size for e in events]))
    slots = VALIDATE_SERVE["max_active"]
    knee = C.knee_rps(prof, slots=slots, mean_new=mean_new,
                      mean_prompt=mean_prompt)
    knee_rho = float(_flags.flag("FLAGS_capacity_knee_rho"))
    sweep = []
    for frac in (0.25, 0.5, 0.75, 0.9, 1.0, 1.1):
        r = max(0.5, knee * frac)
        spec = W.builtin_spec("steady", rate=r, duration_s=duration_s)
        p = C.predict(spec, seed, prof, slots=slots,
                      kv_blocks=VALIDATE_SERVE["kv_blocks"],
                      block_size=VALIDATE_SERVE["block_size"])
        p["over_knee"] = p["rho"] > knee_rho
        sweep.append(p)
    from paddle_tpu.text.models.gpt import GPT, GPTConfig
    net = GPT(GPTConfig.tiny())
    params, _ = net.functional_state()
    param_bytes = float(sum(int(np.prod(v.shape)) * 4
                            for v in params.values()))
    return {
        "tool": "capacity_plan",
        "device": device,
        "profile": prof.as_dict(),
        "knee_rps": round(knee, 3),
        "knee_rho": knee_rho,
        "sweep": sweep,
        "fleet": {"param_bytes": param_bytes,
                  "publish_wire_ms_x4_replicas":
                      round(C.publish_wire_ms(param_bytes, 4), 3)},
    }


# ---------------------------------------------------------------------------
# closed-loop validation (the proof)
# ---------------------------------------------------------------------------

def _err_pct(pred, obs):
    if pred in (None, 0) or obs is None:
        return None
    return round(100.0 * abs(obs - pred) / abs(pred), 1)


def validate(evidence_path=None, update_evidence=True):
    """Calibrate, predict each builtin spec, replay it through the real
    harness with the hub scoring, and hold the observation to the
    bands. Returns the capacity_validation section (ok=False if any
    metric lands outside its band)."""
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.static import capacity as C

    band50 = float(_flags.flag("FLAGS_capacity_p50_band_pct"))
    band99 = float(_flags.flag("FLAGS_capacity_p99_band_pct"))
    attempts = 0
    while True:
        attempts += 1
        prof = C.calibrate_cpu(VALIDATE_SERVE)
        section = _validate_once(prof, band50, band99)
        # CPU wall-clock drifts at minute scale with background load; a
        # profile calibrated in a slow window mispredicts a fast one.
        # One recalibrate-and-retry (fresh profile, fresh observations —
        # never fitted on the scored runs) absorbs that drift.
        if section["ok"] or attempts >= 2:
            break
    section["attempts"] = attempts
    section["profile"] = prof.as_dict()
    if update_evidence:
        path = evidence_path or os.path.join(REPO, "HLO_EVIDENCE.json")
        ev = _load_evidence(path)
        ev["graphs"]["capacity_validation"] = section
        with open(path, "w") as f:
            json.dump(ev, f, indent=1, sort_keys=True)
            f.write("\n")
    return section


def _validate_once(prof, band50, band99):
    from paddle_tpu.core import telemetry
    from paddle_tpu.static import capacity as C
    from paddle_tpu.traffic import harness as H
    from paddle_tpu.traffic import workload as W

    specs = {}
    worst = {"p50_class": 0.0, "p99_class": 0.0}
    ok = True
    for name in VALIDATE_SPECS:
        spec = W.builtin_spec(name, rate=VALIDATE_RATE,
                              duration_s=VALIDATE_DURATION_S)
        pred = C.predict(spec, VALIDATE_SEED, prof,
                         slots=VALIDATE_SERVE["max_active"],
                         kv_blocks=VALIDATE_SERVE["kv_blocks"],
                         block_size=VALIDATE_SERVE["block_size"])
        hub = telemetry.TelemetryHub(eval_s=5.0)
        try:
            obs = H.run_spec(spec, seed=VALIDATE_SEED,
                             serve_cfg=VALIDATE_SERVE, hub=hub)
        finally:
            hub.stop()
        errs = {
            "throughput_rps": _err_pct(pred["throughput_rps"],
                                       obs.throughput_rps),
            "ttft_p50": _err_pct(pred["ttft_ms"]["p50"],
                                 obs.ttft_ms.get("p50")),
            "ttft_p99": _err_pct(pred["ttft_ms"]["p99"],
                                 obs.ttft_ms.get("p99")),
            "token_p50": _err_pct(pred["token_ms"]["p50"],
                                  obs.token_ms.get("p50")),
            "token_p99": _err_pct(pred["token_ms"]["p99"],
                                  obs.token_ms.get("p99")),
        }
        spec_ok = (obs.scored_by == "hub" and obs.errors == 0
                   and obs.completed == obs.events)
        for key, e in errs.items():
            band = band99 if key.endswith("p99") else band50
            cls = "p99_class" if key.endswith("p99") else "p50_class"
            if e is None:
                spec_ok = False
                continue
            worst[cls] = max(worst[cls], e)
            if e > band:
                spec_ok = False
        ok = ok and spec_ok
        specs[name] = {
            "predicted": {k: pred[k] for k in
                          ("throughput_rps", "ttft_ms", "token_ms",
                           "offered_rps", "rho", "knee_rps",
                           "backpressure_ticks", "events")},
            "observed": {"throughput_rps": obs.throughput_rps,
                         "ttft_ms": obs.ttft_ms,
                         "token_ms": obs.token_ms,
                         "completed": obs.completed,
                         "errors": obs.errors,
                         "backpressure_waits": obs.backpressure_waits,
                         "scored_by": obs.scored_by,
                         "schedule_digest": obs.schedule_digest[:16]},
            "err_pct": errs,
            "ok": spec_ok,
        }
    headroom = min(
        band50 / max(worst["p50_class"], band50 / _HEADROOM_CAP),
        band99 / max(worst["p99_class"], band99 / _HEADROOM_CAP))
    return {
        "config": dict(VALIDATE_SERVE, rate_rps=VALIDATE_RATE,
                       duration_s=VALIDATE_DURATION_S,
                       seed=VALIDATE_SEED),
        "bands_pct": {"p50": band50, "p99": band99},
        "specs": specs,
        "worst_err_pct": {k: round(v, 1) for k, v in worst.items()},
        "band_headroom_x": round(headroom if ok else 0.0, 3),
        "ok": ok,
    }


# ---------------------------------------------------------------------------
# self-check (TOOL_CROSS_CHECKS)
# ---------------------------------------------------------------------------

def self_check():
    """Pin flag defaults <-> this tool's knobs <-> docs <->
    committed evidence. Run by framework_lint.check_registered_tools."""
    problems = []
    from paddle_tpu.core import flags as _flags

    for table in (CAPACITY_FLAG_DEFAULTS, TRAFFIC_FLAG_DEFAULTS):
        for name, want in table.items():
            defn = _flags._DEFS.get(name)
            if defn is None:
                problems.append(
                    f"capacity_plan: flag {name} not defined in "
                    "core/flags.py")
            elif defn[1] != want:
                problems.append(
                    f"capacity_plan: default drift for {name} "
                    f"({defn[1]!r} != {want!r}) — update the table here "
                    "and docs/traffic_lab.md together")

    # the validation serve shape must be the harness's default tiny
    # shape — a drift here validates a loop nobody else runs
    import inspect

    from paddle_tpu.traffic import harness as H
    src = inspect.getsource(H.build_tiny_loop)
    for key, want in VALIDATE_SERVE.items():
        token = f'setdefault("{key}", {want})'
        if token not in src:
            problems.append(
                f"capacity_plan: VALIDATE_SERVE[{key!r}]={want} not the "
                f"harness build_tiny_loop default ({token} missing)")

    # docs: flag table rows + the terms the model is explained with
    doc = os.path.join(REPO, "docs", "traffic_lab.md")
    try:
        with open(doc) as f:
            text = f.read()
        for tok in ("capacity_plan", "--validate", "band_headroom_x",
                    "splitmix64",
                    *CAPACITY_FLAG_DEFAULTS, *TRAFFIC_FLAG_DEFAULTS):
            if tok not in text:
                problems.append(
                    f"capacity_plan: docs/traffic_lab.md lost {tok!r}")
    except OSError as e:
        problems.append(f"capacity_plan: cannot read {doc}: {e}")

    # committed evidence: bands recorded there must be the flag bands,
    # and the perf floor gates headroom >= 1.0 (framework_lint)
    try:
        ev = _load_evidence(os.path.join(REPO, "HLO_EVIDENCE.json"))
        cv = ev.get("graphs", {}).get("capacity_validation")
        if cv is None:
            problems.append(
                "capacity_plan: HLO_EVIDENCE.json has no "
                "graphs.capacity_validation — run "
                "`python tools/capacity_plan.py --validate`")
        else:
            for key, flag in (("p50", "FLAGS_capacity_p50_band_pct"),
                              ("p99", "FLAGS_capacity_p99_band_pct")):
                want = CAPACITY_FLAG_DEFAULTS[flag]
                got = cv.get("bands_pct", {}).get(key)
                if got != want:
                    problems.append(
                        f"capacity_plan: evidence band {key}={got} != "
                        f"flag default {want} — re-run --validate")
            for name in VALIDATE_SPECS:
                if name not in cv.get("specs", {}):
                    problems.append(
                        f"capacity_plan: evidence missing validated "
                        f"spec {name!r}")
    except OSError as e:
        problems.append(f"capacity_plan: cannot read evidence: {e}")

    # shared estimator: this tool must not grow a private percentile
    with open(os.path.abspath(__file__)) as f:
        own = f.read()
    if ("def " + "percentile") in own:  # split so the pin can't self-match
        problems.append(
            "capacity_plan: grew a private percentile — use "
            "paddle_tpu.core.slo")
    return problems


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--validate", action="store_true")
    p.add_argument("--evidence",
                   default=os.path.join(REPO, "HLO_EVIDENCE.json"))
    p.add_argument("--device", default="tpu-v3")
    p.add_argument("--rate", type=float, default=None)
    p.add_argument("--no-update", action="store_true",
                   help="validate without rewriting HLO_EVIDENCE.json")
    p.add_argument("--self-check", "--self_check", action="store_true",
                   dest="self_check")
    args = p.parse_args(argv)
    if args.self_check:
        problems = self_check()
        for prob in problems:
            print(f"SELF-CHECK FAIL: {prob}")
        if problems:
            return 1
        print("capacity_plan self-check OK")
        return 0
    if args.validate:
        section = validate(args.evidence,
                           update_evidence=not args.no_update)
        print(json.dumps(section, indent=1, sort_keys=True))
        return 0 if section["ok"] else 1
    print(json.dumps(report(args.evidence, device=args.device,
                            rate=args.rate), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
