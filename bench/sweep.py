"""Knee of an open-loop mix on the chip: the highest mean rate at which
every request is answered and the queue (admitted, not yet dispatched) when
the window closes holds no more than one largest bucket.

  python bench/sweep.py --workload m3vit-small.bursty --rates 600,800,1000 \
      --seconds 20 --seeds 5,6

One process builds the cell once and drives the cell's traffic at each rate
and seed in turn (only ``rate`` changes); one JSON line per run. The cell's traffic
file then takes 0.8 of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import main as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="0")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    cell = bench.resolve(bench.load_benchmark(), args.workload)
    bench.devices(cell["cell"]["chips"])
    bench.enable_cache()
    from harness import loop

    fam = bench.family_module(cell["spec"]["family"])
    seeds = [int(s) for s in args.seeds.split(",")]
    system = fam.System(cell["spec"], seeds[0])
    big = max(cell["spec"]["batch_buckets"])
    for rate, seed in ((float(r), s) for r in args.rates.split(",")
                       for s in seeds):
        system.delivered, system.answers = {}, {}
        traffic = dict(cell["traffic"], rate=rate)
        w = loop.run(system, traffic, args.seconds, seed)
        got = system.delivered
        lat = np.array([(got[i] - w["due_at"][i]) * 1e3 if i in got
                        else 1e9 for i in range(w["offered"])])
        print(json.dumps({
            "rate": rate, "seed": seed, "offered": w["offered"],
            "refused": len(w["refused"]), "open_at_end": w["open_at_end"],
            "queued_at_end": w["queued_at_end"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "submit_lag_p95_ms": 1e3 * w["submit_lag_p95_s"],
            "sustained": bool(not w["refused"] and w["queued_at_end"] <= big),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
