#!/usr/bin/env python3
"""Reads a fault at a cell's own size on the chip (PERF.md gives the
readings): `python3 onchip/tests/run_with_fault.py <fault> <run.py's
arguments>` plants `faults.<fault>` in the program and drives a whole run of
the harness over it.  Not part of the benchmark's own runs."""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
# the faults import the program before run.py has put the checkout on the path
for _p in (os.path.dirname(os.path.dirname(HERE)), HERE):
    sys.path.insert(0, _p)

import faults  # noqa: E402

if __name__ == "__main__":
    faults.FAULTS[sys.argv[1]](setattr)
    spec = importlib.util.spec_from_file_location(
        "onchip_run", os.path.join(os.path.dirname(HERE), "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    run.main(sys.argv[2:])
