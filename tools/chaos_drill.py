#!/usr/bin/env python
"""Standalone chaos-drill runner: the fault-injection scenario matrix.

Thin wrapper over `stark_tpu.chaos` (the same matrix the
``python -m stark_tpu chaos-drill`` subcommand runs), so the drill is
invokable from CI without the CLI's platform setup::

    python tools/chaos_drill.py                 # full matrix
    python tools/chaos_drill.py stall_watchdog  # one scenario
    python tools/chaos_drill.py --workdir /tmp/drill --list

Exit code 0 iff every scenario passes.  Scenario semantics, knobs, and the
failpoint grammar are documented in ``stark_tpu/chaos.py`` and the README
"Robustness" section.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the drill exercises supervision mechanics, not hardware: it runs on the
# CPU unless JAX_PLATFORMS says otherwise
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("scenario", nargs="*", help="scenario names (default: all)")
    parser.add_argument("--workdir", default=None, help="keep artifacts here")
    parser.add_argument("--list", action="store_true", help="list scenarios")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO, format="[%(name)s] %(message)s", stream=sys.stderr
    )
    from stark_tpu import chaos

    if args.list:
        print("\n".join(chaos.SCENARIOS))
        return 0
    return chaos.main(args.scenario or None, args.workdir)


if __name__ == "__main__":
    sys.exit(main())
