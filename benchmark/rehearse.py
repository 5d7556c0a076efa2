#!/usr/bin/env python3
"""A cell's control flow at its tiny preset, on whatever platform JAX selects.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py --workload <name> \\
        --seed <n> --seconds <s> --trace 0 [--manifest <BENCHMARK.json>]

The same code path as ``run.py`` with the configuration's ``tiny`` sizes and
the traffic file's ``tiny`` parameters; the last line carries counts and no
device metric.  A four-chip cell wants
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  ``--manifest`` names
another manifest: a cell that is not committed yet, or a test's copy.  The
measuring command, ``run.py``, has neither option.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--manifest", default=manifest_mod.DEFAULT_MANIFEST)
    own, rest = ap.parse_known_args(argv)
    return run.main(rest, manifest_path=own.manifest, rehearse=True)


if __name__ == "__main__":
    sys.exit(main())
