#!/usr/bin/env python3
"""Readings behind the correctness limit of a cell, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 51 [--control]

For each seed, in one process: build the served system from the seed,
warm it up, serve the cell's mix for ``--seconds``, free it, and judge a
seeded sample of the finished requests by the comparison a run makes
(``check.check``). With ``--control`` the same comparison also judges
the fp8 control on the same prompts and served tokens
(``check.check(control=True)``), which has to come out not ``ok``. It
prints one JSON line per seed: ``program`` and ``control``, each with
``ok``, the numbers compared with their limits, and the gap statistics.
Each limit in the configuration file lies between the largest program
reading and the smallest control reading (``PERF.md``). The benchmark's
own runs do not run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import harness, models, stats, traffic
    from bench.reference import check

    harness.cache_every_program()
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    harness.require_chip(cell["chips"])
    conf = harness.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    model = models.load(conf)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        served = harness.Served(conf, mix, seed, model)
        try:
            served.warm_up()
            run = served.window(args.seconds)
        finally:
            served.close()
        recs = stats.due_in_window(run)
        out = {"seed": seed, "requests": len(recs),
               "unfinished": run["unfinished"]}
        for side in ("program", "control")[:1 + args.control]:
            v = check.check(conf, mix, run, seed,
                            sample_tokens=harness.CHECK_TOKENS,
                            control=side == "control", model=model)
            out[side] = {"ok": v["ok"], "compared": v["compared"],
                         "read": v["read"]}
        out["seconds"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
