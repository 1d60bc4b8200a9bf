#!/usr/bin/env python3
"""The control, on the chip: the cell run with its family's plain
reference, computed with float8 matmul inputs (``reference.py``), in the
program's place.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed, one process runs the cell as ``run.py`` does (weights from
the seed, the window at the cell's own load and length) and then decides
``correct`` by the same checks and limits, with the fp8 reference's
answers over the sampled requests in place of the program's: its tokens
put first at each served position, or its logits rows. One JSON line per
seed, with the control's reading under the checked name and the
program's own beside it (``program_*``). Exits 1 if the control comes out
correct on any seed. The benchmark's own runs do not run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.use_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    peak = json.loads((run.BENCH / "peaks.json").read_text())[
        "devices"][dev.device_kind]
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False, peak,
                           control=True, log=log)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "readings": res["readings"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}),
              flush=True)
        if res["correct"]:
            passed.append(seed)
    if passed:
        log(f"control: came out correct on seeds {passed}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
