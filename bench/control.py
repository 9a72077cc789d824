"""Readings of the program, of its fp8 control and of planted faults, over
many seeds in one process (the benchmark's own runs do none of this):

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51 \\
        --fault half_batch

Each seed is a whole run of the cell (new weights, engine, window,
reference). Without ``--fault`` the fp8 control takes the program's place
in the check that decides ``correct``, and the program's own readings come
back beside it under ``program_<name>``; with ``--fault`` the program runs
with that fault of ``faults.py`` planted under its timed path. One JSON
line per seed. The limits in ``bench/configs/*.json`` are set from these
readings (see PERF.md).
"""

import argparse
import json
import sys

import faults
import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    hook = faults.FAULTS[args.fault] if args.fault else None
    for seed in (int(s) for s in args.seeds.split(",")):
        res = R.run(args.workload, seed, args.seconds, False,
                    engine_hook=hook, control=hook is None)
        if res is None:
            return 1
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": res["correct"],
                          "served": res["served"],
                          "readings": res["readings"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
