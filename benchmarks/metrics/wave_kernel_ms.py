"""Device time of one launch of the shared-scan wave kernel: the summed
time of the trace's device ops called ``sdot_wave`` (the kernel's HLO
instruction name, ``.N`` suffix dropped) over the wave-kernel launches
the slice's records count. None where the trace shows no such op (a
program that does not name its kernel, a cell without a storm)."""
import re

LAYER = "kernels (ops/pallas_groupby.py, ops/pallas_wave.py, XLA tiers)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "stmts_per_s"

KERNEL = "sdot_wave"


def compute(run):
    if not run["trace"]:
        return None
    seconds = [s for name, s in run["trace"]["device_ops"]
               if re.sub(r"\.\d+$", "", name) == KERNEL]
    launches = sum(int(r.get("kernel_launches") or 0)
                   for r in run["records"])
    if not seconds or not launches:
        return None
    return sum(seconds) * 1000.0 / launches
