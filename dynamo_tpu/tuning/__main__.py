"""CLI: ``python -m dynamo_tpu.tuning`` (also reachable as ``bench.py --tune``).

Runs the closed-loop knob search and writes the trial journal, winning
profile, and gain report under the output directory (default
``bench/results/tune/``). Flags seed from the ``DYN_TUNE_*`` config
cascade, so a TOML ``[tune]`` section or env set the same defaults.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv: list[str] | None = None) -> int:
    from dynamo_tpu.config import load_tune_settings
    from dynamo_tpu.tuning.metrics import TunerMetrics
    from dynamo_tpu.tuning.search import Tuner

    ts = load_tune_settings()
    parser = argparse.ArgumentParser(
        prog="python -m dynamo_tpu.tuning",
        description="closed-loop performance knob auto-tuner",
    )
    parser.add_argument("--preset", default=ts.preset, help="model preset to tune for")
    parser.add_argument("--mode", default=ts.mode, choices=["mock", "jax"],
                        help="probe backend: mock (CPU proxy) or jax (real model)")
    parser.add_argument("--seed", type=int, default=ts.seed)
    parser.add_argument("--rounds", type=int, default=ts.rounds,
                        help="max coordinate-descent rounds")
    parser.add_argument("--requests", type=int, default=ts.requests,
                        help="requests per full-length probe")
    parser.add_argument("--isl", type=int, default=ts.isl)
    parser.add_argument("--osl", type=int, default=ts.osl)
    parser.add_argument("--max-trials", type=int, default=ts.max_trials,
                        help="hard cap on measured probes (0 = unlimited)")
    parser.add_argument("--out-dir", default=ts.out_dir,
                        help="journal/profile/report directory")
    parser.add_argument("--knobs", default=ts.knobs,
                        help="comma list restricting swept knobs")
    args = parser.parse_args(argv)
    settings = type(ts)(
        preset=args.preset, mode=args.mode, seed=args.seed,
        rounds=args.rounds, requests=args.requests, isl=args.isl,
        osl=args.osl, rung_frac=ts.rung_frac, plateau_eps=ts.plateau_eps,
        plateau_rounds=ts.plateau_rounds, max_trials=args.max_trials,
        out_dir=args.out_dir, knobs=args.knobs,
    )
    if args.mode == "jax":
        from dynamo_tpu.compile_cache import enable_compile_cache

        enable_compile_cache()
    tuner = Tuner(settings, metrics=TunerMetrics())
    report = tuner.run()
    print(json.dumps({
        "best_assignment": report["best"]["assignment"],
        "baseline_score": report["baseline"]["score"],
        "best_score": report["best"]["score"],
        "gain": report["gain"],
        "stopped": report["stopped"],
        "trials_measured": report["trials_measured"],
        "trials_cached": report["trials_cached"],
        "burnable_frac": report["burn_down"]["best_burnable_frac"],
        "profile": report["profile_path"],
        "report": report["report_path"],
        "journal": report["journal_path"],
    }, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
