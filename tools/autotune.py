#!/usr/bin/env python
"""Ledger-mining autotuner: emit a parity-gated execution profile.

The repo's ~15 performance knobs (STARK_FUSED_* family toggles, the
X-stream dtype, the MXU precision, the ragged-NUTS scheduler, the fleet
slot/warm-start/mesh trio) each shipped with their own evidence legs —
``bench.py microbench`` rows in ``bench_artifacts/ledger.jsonl``, the
``tools/precision_parity.py`` zoo grid — but nothing reconciled them
into a configuration.  This tool does, in four steps:

1. **Fingerprint** the hardware (`stark_tpu.platform.hardware_fingerprint`).
2. **Mine** the perf ledger for rows matching that fingerprint (legacy
   pre-fingerprint rows match on platform + device_kind + device_count);
   stale-schema rows and fingerprint mismatches are skipped WITH COUNTS
   — silent truncation would read as "no evidence" when the evidence was
   simply unreadable.
3. **Measure fresh** smoke-scale microbench legs for whatever the ledger
   could not answer (fused families, X-dtype legs, nutssched, the
   streaming-fleet leg) — skipped under ``--no-fresh``/``--check``.
4. **Select** the cheapest configuration whose parity cells ALL pass the
   `precision_parity` sweep grid (run here at smoke scale): per-family
   fused toggles on iff measured speedup > 1x, the X-stream dtype
   maximizing measured throughput among parity-eligible dtypes, the
   cheapest parity-passing precision (default < high < highest, with
   ``highest`` inheriting ``high``'s verdict by construction), ragged
   NUTS iff bit-identical AND faster, the fleet trio from their own
   gates.

The result is a versioned JSON profile (`stark_tpu.profile`, atomic
write) at ``bench_artifacts/profiles/<fingerprint>.json``, loaded by
default at every runner/fleet/sampler entry (STARK_PROFILE=path|auto|0;
explicit STARK_* env always wins), plus one honest-null ``autotune:*``
ledger row recording the choice (ess_per_sec is null — the autotuner
measures nothing gateable; ``converged`` carries the parity verdict).

``--check`` is the tier-1 contract smoke: no fresh measurement, a tiny
parity subset (one zoo case x {f32, bf16} x default), profile written
to a temp dir and round-tripped through `load_profile` — proving the
mine/select/emit/load pipeline end to end in seconds.

The process pins STARK_PROFILE=0 for itself: candidate measurement and
parity cells must run on raw knob defaults, never under a previously
emitted profile (an autotuner steered by its own output ratchets).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

# --- mining (pure: unit-tested without jax) ----------------------------

#: microbench family -> the fused-op toggle it evidences.  GLM has no
#: standalone microbench family (its default is on; parity still gates
#: it), logistic's fused op is always-on (no knob).
FAMILY_KNOBS = {
    "lmm": "STARK_FUSED_LMM",
    "irt": "STARK_FUSED_IRT",
    "ordinal": "STARK_FUSED_ORDINAL",
    "robust": "STARK_FUSED_ROBUST",
}

#: the dtype-scan family: X-stream dtype legs are measured on the
#: scatter/stream-dominated LMM op (the family the quantized data plane
#: was built for)
DTYPE_FAMILY = "lmm"


def mine_ledger(path, fingerprint, device_info):
    """Read the RAW ledger and split it into (matching_rows, counts).

    Unlike `stark_tpu.ledger.read_rows` (which silently skips foreign
    lines — right for the gate, wrong for an evidence miner), every
    skipped line is counted: ``torn`` (unparseable), ``stale_schema``
    (a schema other than the current writer's — regenerate, don't
    guess), ``fingerprint_mismatch`` (evidence from other hardware must
    not steer this one).  Rows predating the fingerprint column match
    on platform + device_kind + device_count from ``device_info``.
    """
    from stark_tpu.ledger import LEDGER_SCHEMA

    counts = {
        "matched": 0, "stale_schema": 0, "fingerprint_mismatch": 0,
        "torn": 0, "lines": 0,
    }
    rows = []
    try:
        f = open(path)
    except OSError:
        return rows, counts
    with f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            counts["lines"] += 1
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                counts["torn"] += 1
                continue
            if not isinstance(rec, dict) or rec.get("schema") != LEDGER_SCHEMA:
                counts["stale_schema"] += 1
                continue
            fp = rec.get("fingerprint")
            if fp is not None:
                matched = fp == fingerprint
            else:
                matched = all(
                    rec.get(k) == device_info.get(k)
                    for k in ("platform", "device_kind", "device_count")
                )
            if not matched:
                counts["fingerprint_mismatch"] += 1
                continue
            counts["matched"] += 1
            rows.append(rec)
    return rows, counts


def _fusedvg_key(config):
    """(family, x_dtype) from a ``fusedvg:<family>:...[:x=<dtype>]`` key,
    or None for other series."""
    if not isinstance(config, str) or not config.startswith("fusedvg:"):
        return None
    parts = config.split(":")
    family = parts[1] if len(parts) > 1 else ""
    x_dtype = "f32"
    for p in parts[2:]:
        if p.startswith("x="):
            x_dtype = p[2:]
    return family, x_dtype


def structure_evidence(rows):
    """Latest-wins evidence index from matching ledger rows:

    * ``fusedvg[(family, x_dtype)]`` — fused value-and-grad rows,
    * ``nutssched`` — the ragged-scheduler row,
    * ``fleet[sched]`` — streaming-fleet rows keyed slots / compact /
      slots_warmstart,
    * ``fleet_mesh`` — the device-parallel fleet row.

    Rows are oldest-first in the ledger, so plain overwrites make the
    newest row win per key.
    """
    ev = {"fusedvg": {}, "nutssched": None, "fleet": {}, "fleet_mesh": None}
    for r in rows:
        config = r.get("config", "")
        fk = _fusedvg_key(config)
        if fk is not None:
            ev["fusedvg"][fk] = r
            continue
        if config.startswith("nutssched:"):
            ev["nutssched"] = r
        elif config.startswith("fleet:stream:"):
            for part in config.split(":"):
                if part.startswith("sched="):
                    ev["fleet"][part[len("sched="):]] = r
        elif config.startswith("fleet:mesh:"):
            ev["fleet_mesh"] = r
    return ev


def missing_fresh_legs(evidence, supported_dtypes):
    """The microbench legs a full run must measure because the mined
    ledger could not answer them: ``("fusedvg", family, x_dtype)`` for
    each family toggle and each candidate dtype of the dtype-scan
    family, ``("nutssched",)``, ``("fleet_stream",)``.  Pure — the
    fingerprint-mismatch fallback contract (mismatched history == no
    history == fresh measurement) is unit-tested on this."""
    legs = []
    for fam in FAMILY_KNOBS:
        if (fam, "f32") not in evidence["fusedvg"]:
            legs.append(("fusedvg", fam, None))
    for dt in supported_dtypes:
        if dt != "f32" and (DTYPE_FAMILY, dt) not in evidence["fusedvg"]:
            legs.append(("fusedvg", DTYPE_FAMILY, dt))
    if evidence["nutssched"] is None:
        legs.append(("nutssched",))
    if not evidence["fleet"]:
        legs.append(("fleet_stream",))
    return legs


# --- selection (pure: unit-tested without jax) -------------------------


def select_config(evidence, parity_rows, supported_dtypes):
    """The cheapest parity-passing knob configuration given the
    evidence.  Returns ``(knobs, parity, rationale)`` where ``knobs``
    is the CANDIDATE_SPACE-valued dict the profile carries, ``parity``
    the verdict dict recorded in (and re-checked at every load of) the
    profile, ``rationale`` the per-knob evidence summary for the
    artifact/ledger row.

    Parity eligibility is per (x_dtype, precision) cell set: a dtype or
    precision with ANY failing zoo cell — or with no coverage at all in
    the grid that ran — is ineligible.  ``highest`` inherits ``high``'s
    verdict (more internal precision than the band was calibrated
    against, by design) and is never selected (never cheapest).
    """

    def cells(d, p):
        if p == "highest":
            p = "high"
        return [
            r for r in parity_rows
            if r.get("x_dtype") == d and r.get("precision") == p
        ]

    def eligible(d, p):
        cs = cells(d, p)
        return bool(cs) and all(r.get("ok") for r in cs)

    rationale = {}
    knobs = {}

    # per-family fused toggles: on iff measured fused-vs-autodiff
    # speedup beats 1x (missing evidence -> the built-in default: off).
    # GLM's built-in default is ON and it has no microbench family; it
    # stays on, gated by its parity cells like every other op.
    knobs["STARK_FUSED_GLM"] = "1"
    for fam, knob in FAMILY_KNOBS.items():
        row = evidence["fusedvg"].get((fam, "f32"))
        sp = row.get("speedup_vs_autodiff") if row else None
        on = bool(sp is not None and sp > 1.0)
        knobs[knob] = "1" if on else "0"
        rationale[knob] = {"speedup_vs_autodiff": sp}

    # X-stream dtype: the measured throughput ratio of the dtype-scan
    # family's fused op at dtype d over its f32 stream, restricted to
    # parity-eligible dtypes; ratios within 5% of f32 stay f32 (a wash
    # must not buy precision risk)
    base = evidence["fusedvg"].get((DTYPE_FAMILY, "f32"))
    best_d, best_ratio = "f32", 1.0
    dtype_ratios = {}
    for d in supported_dtypes:
        if d == "f32":
            continue
        if not (eligible(d, "default") or eligible(d, "high")):
            continue
        row = evidence["fusedvg"].get((DTYPE_FAMILY, d))
        if row is None:
            continue
        ratio = None
        rate_d = row.get("ess_per_sec") or row.get("value")
        rate_0 = (base or {}).get("ess_per_sec") or (base or {}).get("value")
        if rate_d and rate_0:
            ratio = rate_d / rate_0
        elif row.get("speedup_vs_f32x"):
            ratio = row["speedup_vs_f32x"]
        if ratio is None:
            continue
        dtype_ratios[d] = round(ratio, 3)
        if ratio > max(best_ratio * 1.05, 1.05):
            best_d, best_ratio = d, ratio
    if not (eligible(best_d, "default") or eligible(best_d, "high")):
        # the winning dtype lost parity (or f32 itself has no passing
        # precision): fall back to f32 before failing outright
        best_d, best_ratio = "f32", 1.0
    knobs["STARK_FUSED_X_DTYPE"] = best_d
    rationale["STARK_FUSED_X_DTYPE"] = {
        "ratios_vs_f32": dtype_ratios, "chosen_ratio": round(best_ratio, 3),
    }

    # precision: cheapest parity-passing for the chosen dtype
    precision, parity_ok = None, False
    for p in ("default", "high"):
        if eligible(best_d, p):
            precision, parity_ok = p, True
            break
    knobs["STARK_FUSED_PRECISION"] = precision or "high"

    # ragged NUTS: bit identity is the admission ticket, speedup the
    # reason (either missing -> the safe default: legacy scheduling)
    ns = evidence["nutssched"]
    ragged = bool(
        ns
        and ns.get("bit_identical")
        and (ns.get("speedup_vs_legacy") or 0) > 1.0
    )
    knobs["STARK_RAGGED_NUTS"] = "1" if ragged else "0"
    rationale["STARK_RAGGED_NUTS"] = {
        "bit_identical": ns.get("bit_identical") if ns else None,
        "speedup_vs_legacy": ns.get("speedup_vs_legacy") if ns else None,
    }

    # fleet trio, each from its own committed gate vocabulary
    slots = evidence["fleet"].get("slots")
    compact = evidence["fleet"].get("compact")
    slots_on = bool(
        slots
        and slots.get("converged")
        and slots.get("ess_per_sec") is not None
        and (
            compact is None
            or compact.get("ess_per_sec") is None
            or slots["ess_per_sec"] >= compact["ess_per_sec"]
        )
    )
    knobs["STARK_FLEET_SLOTS"] = "1" if slots_on else "0"
    ws = evidence["fleet"].get("slots_warmstart")
    ws_speedup = ws.get("warmstart_speedup") if ws else None
    knobs["STARK_FLEET_WARMSTART"] = (
        "1" if slots_on and ws_speedup is not None and ws_speedup > 1.0
        else "0"
    )
    mesh = evidence["fleet_mesh"]
    mesh_on = bool(
        mesh
        and mesh.get("converged")
        and (mesh.get("speedup_vs_single_device") or 0) >= 2.0
    )
    knobs["STARK_FLEET_MESH"] = "1" if mesh_on else "0"
    rationale["STARK_FLEET_SLOTS"] = {
        "slots_rate": slots.get("ess_per_sec") if slots else None,
        "compact_rate": compact.get("ess_per_sec") if compact else None,
    }
    rationale["STARK_FLEET_WARMSTART"] = {"warmstart_speedup": ws_speedup}
    rationale["STARK_FLEET_MESH"] = {
        "speedup_vs_single_device": (
            mesh.get("speedup_vs_single_device") if mesh else None
        ),
    }

    chosen = cells(best_d, knobs["STARK_FUSED_PRECISION"])
    parity = {
        "ok": parity_ok,
        "x_dtype": best_d,
        "precision": knobs["STARK_FUSED_PRECISION"],
        "cells": len(chosen),
        "failed": sorted(
            f"{r.get('op')}:{r.get('x_dtype')}:{r.get('precision')}"
            for r in chosen if not r.get("ok")
        ),
    }
    return knobs, parity, rationale


# --- measurement / orchestration ---------------------------------------


def _run_parity(check):
    """The smoke-scale parity grid for this run: (rows, scale dict).
    ``--check`` shrinks to one zoo case x {f32, bf16} x default — the
    harness-pipeline smoke; the full run covers every case and dtype at
    PARITY_SWEEP_* smoke scale (overridable via env, as everywhere)."""
    if check:
        for k, v in (("PARITY_SWEEP_N", "512"), ("PARITY_SWEEP_D", "4"),
                     ("PARITY_SWEEP_G", "20")):
            os.environ.setdefault(k, v)
    else:
        for k, v in (("PARITY_SWEEP_N", "4000"), ("PARITY_SWEEP_D", "8"),
                     ("PARITY_SWEEP_G", "50")):
            os.environ.setdefault(k, v)
    import importlib

    import precision_parity

    importlib.reload(precision_parity)  # constants are read at import
    scale = {
        "n": precision_parity.SWEEP_N,
        "d": precision_parity.SWEEP_D,
        "g": precision_parity.SWEEP_G,
    }
    if check:
        cases = precision_parity.zoo_cases()[:1]
        rows, _ = precision_parity.run_sweep(
            x_dtypes=("f32", "bf16"), precisions=("default",), cases=cases,
        )
    else:
        rows, _ = precision_parity.run_sweep()
    return rows, scale


def _measure_fresh(legs):
    """Run the smoke-scale microbench legs the ledger could not answer
    and fold their rows into the evidence index shape.  Each leg is
    best-effort: a broken leg records nothing (its knob then keeps the
    built-in default), never aborts the tune."""
    os.environ.setdefault("BENCH_FUSEDVG_SCALE", "0.05")
    os.environ.setdefault("BENCH_NUTSSCHED_SCALE", "0.25")
    from bench import res_row
    from stark_tpu import benchmarks as bmarks

    fresh = {"fusedvg": {}, "nutssched": None, "fleet": {}}
    ran = []
    for leg in legs:
        try:
            if leg[0] == "fusedvg":
                _, fam, xdt = leg
                row = res_row(
                    bmarks.bench_fused_value_and_grad(fam, x_dtype=xdt)
                )
                row["ess_per_sec"] = row.get("value")
                fresh["fusedvg"][(fam, xdt or "f32")] = row
            elif leg[0] == "nutssched":
                row = res_row(bmarks.bench_nuts_sched())
                fresh["nutssched"] = row
            elif leg[0] == "fleet_stream":
                r = bmarks.bench_fleet_stream(
                    problems=4, chains=2, num_warmup=100, block_size=20,
                    max_blocks=20, ess_target=30.0, max_batch=2,
                )
                row = res_row(r)
                row["ess_per_sec"] = row.get("value")
                fresh["fleet"]["slots"] = row
                legacy = row.get("legacy") or {}
                if legacy:
                    fresh["fleet"]["compact"] = legacy
                ws = row.get("warmstart") or {}
                if ws:
                    fresh["fleet"]["slots_warmstart"] = ws
            ran.append(":".join(str(p) for p in leg if p))
        except Exception as e:  # noqa: BLE001 — one broken leg must not
            # abort the tune; its knob keeps the built-in default
            print(f"[autotune] fresh leg {leg} failed: {e!r}",
                  file=sys.stderr)
    return fresh, ran


def _merge_evidence(mined, fresh):
    """Fresh measurement fills only the holes — a mined row from THIS
    fingerprint is real history and outranks a smoke-scale fresh leg."""
    out = {
        "fusedvg": {**fresh["fusedvg"], **mined["fusedvg"]},
        "nutssched": mined["nutssched"] or fresh["nutssched"],
        "fleet": {**fresh["fleet"], **mined["fleet"]},
        "fleet_mesh": mined.get("fleet_mesh"),
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--check", action="store_true",
        help="tier-1 contract smoke: no fresh measurement, tiny parity "
        "subset, profile written to a temp dir and round-trip loaded",
    )
    ap.add_argument(
        "--no-fresh", action="store_true",
        help="mine + parity only; never run fresh microbench legs",
    )
    ap.add_argument(
        "--model", default="hier_logistic",
        help="model tag recorded in the profile (default: the flagship)",
    )
    ap.add_argument(
        "--out", default=None,
        help="profile path (default: bench_artifacts/profiles/"
        "<fingerprint>.json; --check defaults to a temp dir)",
    )
    ap.add_argument(
        "--ledger", default=None,
        help="ledger to mine (default: the STARK_PERF_LEDGER resolution)",
    )
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    # the autotuner must measure RAW knob defaults: never run candidate
    # legs (or parity cells) under a previously emitted profile
    os.environ["STARK_PROFILE"] = "0"

    from stark_tpu import ledger, profile, telemetry
    from stark_tpu.platform import hardware_fingerprint

    fingerprint = hardware_fingerprint()
    info = telemetry.device_info()
    from stark_tpu.platform import _dtype_support

    backend_ok = set(_dtype_support())
    supported = [
        d for d in profile.CANDIDATE_SPACE["STARK_FUSED_X_DTYPE"]
        if d in backend_ok
    ]

    ledger_path = args.ledger or ledger.default_ledger_path() or os.path.join(
        REPO, "bench_artifacts", "ledger.jsonl"
    )
    mined_rows, counts = mine_ledger(ledger_path, fingerprint, info)
    mined = structure_evidence(mined_rows)
    print(
        f"[autotune] ledger {ledger_path}: {counts['matched']} matching "
        f"row(s) ({counts['stale_schema']} stale-schema, "
        f"{counts['fingerprint_mismatch']} fingerprint-mismatch, "
        f"{counts['torn']} torn line(s) skipped)",
        file=sys.stderr,
    )

    fresh_ran = []
    if args.check or args.no_fresh:
        evidence = _merge_evidence(
            mined, {"fusedvg": {}, "nutssched": None, "fleet": {}}
        )
    else:
        legs = missing_fresh_legs(mined, supported)
        fresh, fresh_ran = _measure_fresh(legs)
        evidence = _merge_evidence(mined, fresh)

    parity_rows, parity_scale = _run_parity(args.check)
    knobs, parity, rationale = select_config(evidence, parity_rows, supported)
    parity["scale"] = parity_scale

    out_path = args.out
    if out_path is None and args.check:
        out_path = os.path.join(
            tempfile.mkdtemp(prefix="autotune_check_"),
            f"{fingerprint}.json",
        )

    summary = {
        "fingerprint": fingerprint,
        "knobs": knobs,
        "parity_ok": parity["ok"],
        "parity_failed": parity["failed"],
        "mined_rows": counts["matched"],
        "stale_rows_skipped": counts["stale_schema"],
        "fingerprint_mismatch_rows": counts["fingerprint_mismatch"],
        "fresh_legs": fresh_ran,
        "wall_s": round(time.perf_counter() - t0, 1),
    }

    if not parity["ok"]:
        # no profile: an emitted-but-refused-at-load profile would be
        # dead weight, and a silently applied parity-failing one is the
        # exact failure mode the gate exists to prevent
        summary["profile"] = None
        print(json.dumps(summary, indent=1))
        print("[autotune] FAILED: no parity-passing configuration",
              file=sys.stderr)
        return 1

    prof = profile.new_profile(
        fingerprint=fingerprint,
        knobs=knobs,
        model=args.model,
        parity=parity,
        evidence={
            "rationale": rationale,
            "mined_rows": counts["matched"],
            "stale_rows_skipped": counts["stale_schema"],
            "fingerprint_mismatch_rows": counts["fingerprint_mismatch"],
            "fresh_legs": fresh_ran,
            "ledger": ledger_path,
        },
        source="tools/autotune.py" + (" --check" if args.check else ""),
    )
    path = profile.write_profile(prof, out_path)
    loaded = profile.load_profile(path)  # round-trip: emit must load
    assert loaded["id"] == prof["id"]
    summary["profile"] = prof["id"]
    summary["path"] = path

    if not args.check:
        # one honest-null ledger row records the CHOICE: the autotuner
        # measures nothing gateable, so ess_per_sec stays null (never
        # 0.0) and ``converged`` carries the parity verdict
        row = ledger.make_row(
            source="tools/autotune.py",
            config=f"autotune:{info['platform']}",
            bench={
                "value": None,
                "converged": parity["ok"],
                "wall_s": summary["wall_s"],
                "profile": prof["id"],
            },
        )
        row.update({
            "chosen_x_dtype": knobs["STARK_FUSED_X_DTYPE"],
            "chosen_precision": knobs["STARK_FUSED_PRECISION"],
            "parity_cells": parity["cells"],
            "mined_rows": counts["matched"],
            "stale_rows_skipped": counts["stale_schema"],
            "fingerprint_mismatch_rows": counts["fingerprint_mismatch"],
            "fresh_legs": len(fresh_ran),
        })
        try:
            ledger.append_row(row, ledger_path)
            summary["ledger_row"] = True
        except Exception as e:  # noqa: BLE001 — the row is provenance,
            # not the product; a full disk must not fail the tune
            print(f"[autotune] ledger append failed: {e!r}", file=sys.stderr)
            summary["ledger_row"] = False

    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
