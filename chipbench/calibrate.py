"""The readings a cell's limits are set from, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

runs the cell once per seed as ``run.py`` does (set-up, the window at
the cell's own load, the read-back) and prints one JSON line per seed
with two readings of every number compared: the program's, and the
control's, whose answers stand in the program's place (``reference.py``),
beside the run's end-to-end metrics and device. The program's are the
lower readings, the control's the upper. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import run, spec  # noqa: E402


def readings(cell, seed: int, seconds: float, **kw) -> dict:
    o = run.execute(cell, seed, seconds, False, **kw)
    program, control = run.judge(o), run.judge(o, control=True)
    return {"seed": seed, "attempted": o.result["attempted"],
            "failed": o.result["failed"], "metrics": o.result["metrics"],
            "device": o.result["device"],
            "program": {k: c["value"] for k, c in program.items()},
            "control": {k: c["value"] for k, c in control.items()},
            "program_correct": run.is_correct(program),
            "control_correct": run.is_correct(control)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for i, seed in enumerate(args.seeds):
        try:
            # The first seed's set-up counts from the start of the process,
            # as in run.py; each later one from its own start.
            out = readings(cell, seed, args.seconds,
                           t_start=run.T_START if i == 0
                           else time.perf_counter())
        except run.NoChip as e:
            run.log(f"cannot measure: {e}")
            return 2
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
