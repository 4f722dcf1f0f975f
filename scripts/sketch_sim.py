#!/usr/bin/env python
"""How often a data seed leaves the sketch deployment's 5 % at each HLL
precision, on the host alone: the three 10,000-group sketch columns of
``benchmarks/statements/tpch_sketch4.json`` (``uq_supplier``'s ``custs``
and ``parts``, ``uq_supplier_1995``'s ``custs``) over key sets drawn as
``tools/tpch.generate`` draws them, in plain numpy with ``ops/hll.py``'s
hash, register and rho, and ``estimate``'s three branches in float64.

    python scripts/sketch_sim.py OUT.json SEEDS [FIRST_SEED] [LOG2M,...] [coupons]

``coupons`` answers as the sparse form does (``ops.hll.estimate_sums``):
a group of up to ``3 m / 16`` distinct coupons — the register, rho and
the low bits of the hash an int32 has left — is counted by them, and
only a larger one estimated from its registers; without it, the
registers' estimate for every group (what the dense forms answer, and
what the sparse form answered before it kept coupons).

It draws the generator's DISTRIBUTIONS (parts uniform, a line's supplier
one of its part's four, an order's customer uniform, 1-7 lines an order,
ship date = order date + 1..121 days), not its random stream: a
simulation seed is no ``--seed`` of the benchmark, and what it gives is
counts — how many of N data sets have a group outside
``numpy.allclose(rtol=0.05)`` (the harness's inequality), which column,
how many groups, how many distinct values the worst group has and how
many registers it shares. The two ranked statements return 20 groups of
7 to 60 values each and are left out. ``PERF.md`` section 4 (PR 35)
holds the table this printed for ``sketch_sim.py OUT 60`` (every data
set: ``scripts/sketch_sim_60.json``) and for ``sketch_sim.py OUT 20 101
11,12,13,14 coupons`` (``scripts/sketch_sim_coupons_20.json``); the chip's own reading at each precision
over one built store is ``scripts/sketch_precision.py``.
"""

import json
import sys

import numpy as np

RTOL = 0.05
SF = 1.0


def fmix32(x):
    """``ops.hll._murmur_fmix32`` on uint32."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def draw(seed):
    """(l_suppkey, l_partkey, o_custkey, ship day) per line, as
    ``tools.tpch.generate`` at SF 1 distributes them."""
    r = np.random.default_rng(seed)
    n_orders, n_cust = int(1_500_000 * SF), int(150_000 * SF)
    n_part, n_supp = int(200_000 * SF), int(10_000 * SF)
    o_day = r.integers(0, 2406, n_orders)
    o_cust = r.integers(1, n_cust + 1, n_orders)
    lines_per = r.integers(1, 8, n_orders)
    n_li = int(lines_per.sum())
    ship = np.repeat(o_day, lines_per) + r.integers(1, 122, n_li)
    part = r.integers(1, n_part + 1, n_li)
    supp = ((part + r.integers(0, 4, n_li) * (n_supp // 4 + 1)) % n_supp) + 1
    return supp, part, np.repeat(o_cust, lines_per), ship


def rho_bits(log2m):
    """``ops.hll._rho_bits``."""
    return (32 - log2m + 1).bit_length()


def coupon_counts(group, h, n_groups, log2m):
    """Per group its distinct coupons (``ops.hll.packed_registers``)."""
    b = rho_bits(log2m)
    t = max(0, 31 - log2m - b)
    w = (h >> np.uint32(log2m)).astype(np.int64)
    width = 32 - log2m
    rho = np.where(w == 0, width + 1,
                   width - np.floor(np.log2(np.maximum(w, 1))).astype(np.int64))
    reg = (h & np.uint32((1 << log2m) - 1)).astype(np.int64)
    coupon = (((reg << b) | rho) << t) | (w & ((1 << t) - 1))
    pair = np.unique((group.astype(np.int64) << 32) | coupon)
    return np.bincount(pair >> 32, minlength=n_groups).astype(np.float64)


def estimates(group, h, n_groups, log2m):
    """Per group (HLL estimate rounded, live registers) from the DISTINCT
    (group, value) pairs' hashes ``h``."""
    m = 1 << log2m
    reg = (h & np.uint32(m - 1)).astype(np.int64)
    w = (h >> np.uint32(log2m)).astype(np.int64)
    width = 32 - log2m
    # rho: 1-based position of the first 1 bit of w in `width` bits
    rho = np.where(w == 0, width + 1,
                   width - np.floor(np.log2(np.maximum(w, 1))).astype(np.int64))
    key = np.sort(((group.astype(np.int64) * m + reg) << 6) | rho)
    last = np.append(key[1:] >> 6 != key[:-1] >> 6, True)
    g = (key[last] >> 6) // m
    live = np.bincount(g, minlength=n_groups).astype(np.float64)
    z = np.bincount(g, weights=np.power(2.0, -(key[last] & 63)),
                    minlength=n_groups) + (m - live)
    alpha = 0.7213 / (1 + 1.079 / m)
    e = alpha * m * m / z
    with np.errstate(divide="ignore"):
        lin = m * np.log(m / np.maximum(m - live, 1))
    e = np.where((e <= 2.5 * m) & (live < m), lin, e)
    return np.round(e), live


def column(group, value, n_groups, precisions, coupons=False):
    """{log2m: [groups outside RTOL, worst relative error, the worst
    group's distinct values, the registers it lost to collisions]}."""
    pair = np.unique((group.astype(np.int64) << 32) | value.astype(np.int64))
    g, v = pair >> 32, (pair & 0xFFFFFFFF).astype(np.uint32)
    exact = np.bincount(g, minlength=n_groups).astype(np.float64)
    h = fmix32(v)
    out = {}
    for p in precisions:
        est, live = estimates(g, h, n_groups, p)
        if coupons:
            c = coupon_counts(g, h, n_groups, p)
            est = np.where(c <= 3 * (1 << p) // 16, c, est)
        err = np.abs(est - exact)
        rel = err / np.maximum(exact, 1)
        worst = int(np.argmax(rel))
        out[p] = [int((err > 1e-8 + RTOL * exact).sum()), float(rel[worst]),
                  int(exact[worst]), int(exact[worst] - live[worst])]
    return out


def main():
    out_path, n_seeds = sys.argv[1], int(sys.argv[2])
    first = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    precisions = [int(p) for p in sys.argv[4].split(",")] \
        if len(sys.argv) > 4 else [11, 12, 13, 14, 15, 16]
    coupons = sys.argv[5:] == ["coupons"]
    doc = {"rtol": RTOL, "sf": SF, "precisions": precisions,
           "coupons": coupons, "seeds": []}
    for seed in range(first, first + n_seeds):
        supp, part, cust, ship = draw(seed)
        n_groups = int(10_000 * SF) + 1
        # 1995-01-01 .. 1995-12-31 in days from 1992-01-01
        y95 = (ship >= 1096) & (ship < 1461)
        rec = {"seed": seed, "columns": {
            "uq_supplier.custs": column(supp, cust, n_groups, precisions,
                                        coupons),
            "uq_supplier.parts": column(supp, part, n_groups, precisions,
                                        coupons),
            "uq_supplier_1995.custs": column(supp[y95], cust[y95], n_groups,
                                             precisions, coupons)}}
        doc["seeds"].append(rec)
        print(json.dumps(rec), flush=True)
    doc["data_sets_outside"] = {
        str(p): sum(any(c[p][0] for c in s["columns"].values())
                    for s in doc["seeds"]) for p in precisions}
    doc["by_column"] = {
        name: {str(p): sum(bool(s["columns"][name][p][0])
                           for s in doc["seeds"]) for p in precisions}
        for name in doc["seeds"][0]["columns"]}
    print(json.dumps({"data_sets": n_seeds,
                      "outside": doc["data_sets_outside"],
                      "by_column": doc["by_column"]}), flush=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
