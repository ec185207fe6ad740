"""Run a cell's control, or the program, on several seeds in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 [--program]

Each seed is one short run of the cell at its own size and load, with
the reference's control (``bench/reference``: phase barriers kept per
channel) in the comparison, or with ``--program`` the plain reference as
in a benchmark run.  Prints one line per seed with every compared number;
the benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    label = "program" if args.program else "control"
    for seed in (int(s) for s in args.seeds.split(",")):
        out = io.StringIO()
        rc = run.run_cell(args.workload, seed, args.seconds, False,
                          control=not args.program, out=out)
        if rc:
            return rc
        result = json.loads(out.getvalue().strip().splitlines()[-1])
        readings = {k: v["value"] for k, v in result["checks"].items()}
        print(f"{label} seed={seed} correct={result['correct']} "
              f"{json.dumps(readings)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
