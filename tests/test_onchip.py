"""The on-chip benchmark's own tests (`onchip/tests`), collected by tier-1.

The driver's command collects `tests/`; the benchmark's tests live beside the
benchmark, because a benchmark PR may add files only there.  This one file
imports them, so that a PR that breaks a manifest name, the trace reduction or
a reader learns it here and not from the driver.  One file on purpose: under
`--dist loadfile` it stays on one worker, and these tests share
`onchip/out/`.
"""

import glob
import importlib.util
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TESTS = os.path.join(_ROOT, "onchip", "tests")

for _path in sorted(glob.glob(os.path.join(_TESTS, "test_*.py"))):
    _name = os.path.splitext(os.path.basename(_path))[0]
    _spec = importlib.util.spec_from_file_location(f"onchip_tests_{_name}",
                                                   _path)
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    for _k, _v in vars(_mod).items():
        if _k.startswith("_"):
            continue
        # two files with a test of one name would silently lose one
        if _k.startswith("test_") and _k in globals():
            raise ImportError(f"onchip/tests: {_k} is defined twice")
        globals()[_k] = _v
