#!/usr/bin/env python3
"""Run one cell with a fault of faults.py planted underneath: the
control of "How `correct` is decided".  The result line must say
"correct": false.  For the builder, on the chip, at the cell's own
size; never part of a benchmark run.

    python3 benchmark/control.py --fault parity_flip \
        --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

if __name__ == "__main__":
    import argparse

    import faults
    import run
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=sorted(faults.FAULTS))
    known, rest = ap.parse_known_args()
    if "--rehearse" in rest:
        os.environ["JAX_PLATFORMS"] = "cpu"
    faults.FAULTS[known.fault]()
    print(f"control: fault {known.fault} planted", file=sys.stderr)
    # a control that crashes has failed: cli() says so and leaves
    run.cli(rest + ["--trace", "0"])
