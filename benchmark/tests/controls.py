#!/usr/bin/env python3
"""The controls and faults at a cell's own size, on the chip.

    python3 benchmark/tests/controls.py --workload <cell> --seconds 8 \
        --seeds 11 12 13 [--faults lose_shard bad_digest ...]

One run per (fault, seed), each a whole run of `run.py` with `faulty_serve.py`
as the serving process, and one sound run per seed.  Prints one line per run:
the fault, the seed, `correct`, and the numbers compared.  Exits 0 when every
sound run read correct and every faulty run did not.

For a cell that hides shards (`hide_shards` in its traffic file) name its own
faults: `--faults no_hide flip_rebuilt flip_get`.  `no_hide` breaks the
guarantee the cell states (every read finds two data shards gone) in the
parent, not in the server: the step looks at the drives and hides nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

FAULTS = ("lose_shard", "bad_digest", "flip_parity", "flip_get")
HIDE_SHARDS = run.hide_shards


def look_only(srv, seed, wl, cfg, heads):
    """`run.hide_shards` that takes nothing away."""
    return HIDE_SHARDS(srv, seed, dict(wl, hide_shards=0), cfg, heads)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=list(FAULTS))
    args = ap.parse_args()
    as_expected = True
    for fault in [None, *args.faults]:
        for seed in args.seeds:
            os.environ.pop("BENCH_FAULT", None)
            served = fault not in (None, "no_hide")
            if served:
                os.environ["BENCH_FAULT"] = fault
            run.hide_shards = look_only if fault == "no_hide" else HIDE_SHARDS
            result = run.run_cell(
                args.workload, seed, args.seconds, False,
                serve_script=os.path.join(
                    HERE, "faulty_serve.py") if served else os.path.join(
                    run.HERE, "serve.py"))
            if result is None:
                print("controls: no chip", file=sys.stderr)
                return 1
            nums = {k: v["value"] for k, v in result["compared"].items()}
            print(json.dumps({"fault": fault or "none", "seed": seed,
                              "correct": result["correct"], **nums}),
                  flush=True)
            as_expected &= result["correct"] is (fault is None)
    return 0 if as_expected else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except run.RunFailure as e:
        print(f"controls: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
