"""The control of a cell's comparison, on the chip: the plain reference
at ``high`` precision (three bf16 passes, the step below the HIGHEST
that the configuration states) put in the program's place, run through
the whole cell (set-up, window, check) once per seed, in one process.

    python3 bench/control.py --workload c768-solo --seeds 1,2,3 \\
        --seconds 10

Prints one JSON line per seed: the numbers compared, each beside its
limit, and how much was checked. The control has to come out not
correct on every seed. The benchmark's own runs never run this.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)

    def make(job):
        return harness.ReferenceSession(cell.config, job.surface, job.seed,
                                        "high")
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             make_session=make)
        print(json.dumps({"seed": seed,
                          "correct": r["correct"],
                          "check_scope": r["check_scope"],
                          "checked": r["checked"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
