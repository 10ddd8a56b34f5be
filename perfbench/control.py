"""The comparison's control: the reference itself at the next precision down
(money columns and every aggregate in float32), put in the program's place.

    python perfbench/control.py --workload <cell> --seed <n> [--rehearse]

It has to come out as NOT correct: at least one of the cell's numbers over
its limit. It needs no chip and is no part of a benchmark run; it reads (or
makes) the same data ``run.py`` does for that seed, at the cell's own scale.
Exit code 0 where the control failed the comparison as it must, 1 where the
comparison let it pass."""

import argparse
import json
import os
import sys

import reference
import run


def control(cell: dict, data_dir: str) -> dict:
    """Per query, the numbers of the float32 reference against the exact
    one, beside their limits; ``caught`` where one is over its limit."""
    out, caught = {}, False
    for q, spec in cell["queries"].items():
        plain = reference.query(q)
        got = reference.compare(plain(data_dir, "float32"),
                                plain(data_dir, "exact"),
                                spec["quotient_columns"])
        over = [k for k in got if got[k] > spec["limits"][k]]
        caught = caught or bool(over)
        out[q] = {"numbers": got, "limits": spec["limits"], "over": over}
    return {"queries": out, "caught": caught}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = run.find_cell(args.workload)
    data_dir = run.cell_data(cell, args.seed, args.rehearse)[0]
    result = control(cell, data_dir)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "data": os.path.basename(data_dir), **result}))
    return 0 if result["caught"] else 1


if __name__ == "__main__":
    sys.exit(main())
