#!/usr/bin/env python
"""Fit the per-backend unit costs ON the live chip and write them to JSON
(VERDICT r4 item 1: calibrate FIRST, then bench, so the sorted-run
auto-gate, compaction gate, and slot ceilings run measured rather than
assumed the first time the chip answers).

Usage (on the chip, through the chip tool):
    python scripts/calibrate_chip.py OUT.json

Runs on the backend JAX gives and names no platform itself. Writes
{"platform": ..., "device_kind": ..., "fitted": {config-key: seconds},
...} to OUT.json (stdout if omitted). bench.py consumes it via
SDOT_BENCH_UNIT_COSTS=OUT.json.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else None
    import jax
    t0 = time.perf_counter()
    devices = jax.devices()
    init_s = time.perf_counter() - t0

    from spark_druid_olap_tpu.tools.calibrate import calibrate_primitives
    from spark_druid_olap_tpu.utils.config import Config

    cfg = Config()
    n_rows = int(os.environ.get("SDOT_CALIB_ROWS", str(1 << 21)))
    t0 = time.perf_counter()
    fitted = calibrate_primitives(cfg, n_rows=n_rows, apply=False)
    fit_s = time.perf_counter() - t0

    doc = {
        "ok": True,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "backend": jax.default_backend(),
        "device0": str(devices[0]),
        "n_devices": len(devices),
        "init_seconds": round(init_s, 1),
        "fit_seconds": round(fit_s, 1),
        "n_rows": n_rows,
        "fitted": {k: float(v) for k, v in fitted.items()},
    }
    line = json.dumps(doc, indent=2)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
