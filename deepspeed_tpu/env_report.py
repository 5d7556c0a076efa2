"""Environment report (reference env_report.py / ``ds_report`` CLI)."""

from __future__ import annotations

import sys


def main(argv=None) -> int:
    import argparse
    import importlib.metadata as md

    ap = argparse.ArgumentParser(
        "dstpu-report", description=__doc__)
    ap.parse_args(argv)

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "MISSING"

    print("-" * 60)
    print("DeepSpeed-TPU environment report")
    print("-" * 60)
    print(f"python ................ {sys.version.split()[0]}")
    for pkg in ("jax", "flax", "optax"):
        print(f"{pkg} {'.' * (22 - len(pkg))} {version(pkg)}")
    # in-process: the report IS the one process that may hold the chip
    import jax

    devs = jax.devices()
    print(f"backend ............... {devs[0].platform}")
    print(f"devices ............... {len(devs)} x {devs[0].device_kind}")
    print(f"process count ......... {jax.process_count()}")
    print("-" * 60)
    print("native ops:")
    from .ops.op_builder import BUILDERS

    for name, cls in BUILDERS.items():
        b = cls()
        ok = b.is_compatible()
        extra = ""
        if ok and name == "CPUAdamBuilder":
            extra = f" (simd width {b.load().dstpu_simd_width()})"
        print(f"  {b.name:<14} {'OK' if ok else 'UNAVAILABLE'}{extra}")
    print("-" * 60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
