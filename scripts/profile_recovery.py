"""MTTR stage breakdown of the supervision plane (PR 3 + PR 7).

Where does a recovery's time go? Runs the SAME supervised-kill
workloads ``bench.py``'s ``recovery`` block publishes and prints the
per-stage attribution extracted from the supervision EventLog
(supervisor.recovery_stages):

- ``detect``     — fault (the chaos fuse's wall-clock fire time) ->
                   the Supervisor's failure_detected event
- ``reform``     — failure_detected -> the replacement cluster's
                   formation barrier opening
- ``restore``    — cluster_formed -> the trainer publishing its
                   restored checkpoint step (cross-mesh on a resize)
- ``first_step`` — restored -> the first post-restore training step

Two modes (PR 7 adds the elastic leg):

- ``restart`` — the PR 3 baseline: a trainer SIGKILL recovered by
  RestartFromCheckpoint at fixed width.
- ``shrink``  — elastic shrink-by-one: a WHOLE EXECUTOR dropped and
  recovered by ElasticResize reforming at width N-1, no replacement
  awaited. The detect stage collapses here (engine liveness classifies
  the loss instead of waiting out heartbeat_timeout).
- ``both``    — run both and print the comparison (the acceptance bar:
  shrink MTTR materially below full-restart MTTR).

plus the supervision ledger (formations, failure kinds, widths, acked
partitions) and the ``exactly_once`` verdict per run.

The harnesses are imported from bench.py (ONE recovery-measurement
implementation, so the profiler's stage attribution describes the
benched run shape); trainers are CPU-pinned there, so the numbers
track the supervision plane itself, not device bring-up.

Usage (CPU, hermetic):

    JAX_PLATFORMS=cpu \
    python scripts/profile_recovery.py [--mode restart|shrink|both] \
        [--parts 8] [--batch 4] [--kill-step 3] [--reps 1] \
        [--heartbeat-interval 0.25] [--poll-interval 0.1] [--json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STAGES = ("detect_s", "reform_s", "restore_s", "first_step_s")


def _median(values):
    # shared reducer (tensorflowonspark_tpu.metrics_report): one median
    # implementation across bench.py and every profile script
    from tensorflowonspark_tpu.metrics_report import median
    return median(values)


def _run_mode(mode, args):
    """Run one mode for --reps; returns its summary dict or None on a
    failed rep (already reported to stderr)."""
    from bench import _recovery_bench, _shrink_recovery_bench

    runs = []
    for rep in range(args.reps):
        if mode == "restart":
            block = _recovery_bench(
                batch=args.batch, parts=args.parts,
                kill_step=args.kill_step,
                heartbeat_interval=args.heartbeat_interval,
                poll_interval=args.poll_interval)
        else:
            block = _shrink_recovery_bench(
                batch=args.batch, parts=args.parts,
                heartbeat_interval=args.heartbeat_interval,
                poll_interval=args.poll_interval)
        if not block["injection_fired"] or block["stages"] is None:
            print("{} rep {}: injection never fired / no stages: {}"
                  .format(mode, rep, block), file=sys.stderr)
            return None
        runs.append(block)

    return {
        "mode": mode,
        "workload": runs[0]["workload"],
        "reps": args.reps,
        "mttr_s": _median([r["mttr_s"] for r in runs]),
        "stages": {k: _median([r["stages"][k] for r in runs])
                   for k in STAGES},
        "exactly_once": all(r["exactly_once"] for r in runs),
        "formations": [r["formations"] for r in runs],
        "widths": runs[0].get("widths"),
        "runs": runs,
    }


def _print_table(summary):
    w = summary["workload"]
    print("[{}] {} partitions x batch {} ({})".format(
        summary["mode"], w["partitions"], w["batch"], w["policy"]))
    print("reps: {}   exactly_once: {}   formations: {}{}".format(
        summary["reps"], summary["exactly_once"], summary["formations"],
        "   widths: {}".format(summary["widths"])
        if summary.get("widths") else ""))
    print()
    mttr = summary["mttr_s"]
    print("{:<14} {:>10} {:>8}".format("stage", "median_s", "% mttr"))
    for key in STAGES:
        v = summary["stages"][key]
        pct = 100.0 * v / mttr if mttr else 0.0
        print("{:<14} {:>10.3f} {:>7.1f}%".format(
            key[:-2].replace("_", " "), v, pct))
    print("{:<14} {:>10.3f}".format("mttr", mttr))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("restart", "shrink", "both"),
                    default="restart",
                    help="restart: PR 3 fixed-width trainer-kill "
                         "recovery; shrink: elastic shrink-by-one on "
                         "executor loss; both: run and compare")
    ap.add_argument("--parts", type=int, default=8,
                    help="feed partitions (== checkpointed steps)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--kill-step", type=int, default=3,
                    help="restart mode: SIGKILL the trainer after this "
                         "step commits")
    ap.add_argument("--reps", type=int, default=1,
                    help="repeat runs; stage table reports per-rep medians")
    ap.add_argument("--heartbeat-interval", type=float, default=0.25)
    ap.add_argument("--poll-interval", type=float, default=0.1)
    ap.add_argument("--json", action="store_true",
                    help="print one JSON blob instead of the table")
    args = ap.parse_args(argv)

    modes = ("restart", "shrink") if args.mode == "both" else (args.mode,)
    summaries = {}
    for mode in modes:
        summary = _run_mode(mode, args)
        if summary is None:
            return 1
        summaries[mode] = summary

    if args.mode == "both":
        full = summaries["restart"]["mttr_s"]
        part = summaries["shrink"]["mttr_s"]
        summaries["shrink_vs_full_restart_mttr"] = \
            round(part / full, 3) if full and part else None
    if args.json:
        print(json.dumps(summaries if args.mode == "both"
                         else summaries[modes[0]]))
        return 0
    for mode in modes:
        _print_table(summaries[mode])
        print()
    if args.mode == "both":
        ratio = summaries["shrink_vs_full_restart_mttr"]
        print("shrink MTTR / full-restart MTTR: {}".format(
            "{:.3f}".format(ratio) if ratio is not None else "n/a"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
