"""Readings from which the correctness limits are set, on the chip.

  python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

One process builds the cell as a run does; for each seed it makes that
seed's weights and images, serves them with the same engine through a short
window of the cell's own traffic, and compares the sampled answers with the
reference, as ``run.py`` does (the program's reading). Then the control
takes the program's place: the same reference in the nearest precision
below the configuration's (bfloat16 operands for its float32 dots), whose
top-k answers on the same images are compared the same way. The program's
readings over a dozen seeds give each limit's lower end, the control's its
upper end. One JSON line per seed and side; nothing here is part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import main as bench  # noqa: E402


def readings(system, fam, cell: dict, seed: int, seconds: float) -> list:
    from harness import loop

    system.reseed(seed)
    loop.run(system, cell["traffic"], seconds, seed)
    out = [dict(side="program", seed=seed, **fam.check(system))]
    uids = system.sample()
    classes, probs = fam.answers_of(system.reference_logits(uids, "bfloat16"),
                                    cell["spec"]["answers"])
    ctl = fam.compare(classes, probs, system.reference_logits(uids))
    out.append(dict(side="control", seed=seed, n=len(uids), **ctl))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    cell = bench.resolve(bench.load_benchmark(), args.workload)
    bench.devices(cell["cell"]["chips"])
    bench.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    fam = bench.family_module(cell["spec"]["family"])
    system = fam.System(cell["spec"], seeds[0])
    for seed in seeds:
        for row in readings(system, fam, cell, seed, args.seconds):
            print(json.dumps(dict(workload=args.workload, **row)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
