#!/usr/bin/env python
"""Late materialization's stage alone, on the backend JAX gives: how c
columns' survivors reach the static [M] prefix out of N scanned rows.

    python scripts/micro_compact.py [OUT.json [SHRINK [SHAPE,...]]]

(``SHRINK`` divides every shape, for a rehearsal off the chip; the shape
names pick some of ``SHAPES``. The ``*_observed`` shapes time the
``unique`` spelling alone: every form of all four shapes compiles for
over ten minutes.)

Three forms at each shape (host clock around ``block_until_ready``, the
median of 5 rounds of 4 calls; ms):

- ``gather``: sort the row positions, then one [M]-probe gather a column
  (``CompactScanContext.keep``);
- ``sort``: the columns ride the sort as payloads, the prefix is a slice
  (``CompactScanContext.taken``);
- ``packed``: sort the row positions, then ONE gather of rows packed
  [N, c] (bitcast to int32).

each under two spellings of "survivors first, in row order": a STABLE sort
keyed on dead-or-alive (``*_stable``; the gather form rides a row index),
and a plain sort on the one key ``row + N * dead``, which is unique, so
needs no stability and is its own row index (``*_unique``). Then the sort
with 1 and 3 payloads, whose slope is ``sort.payload.seconds.per.row`` at
that N. ``ops.scan.carries_by_sort`` rests on these numbers (PERF.md,
PR 29).
"""

import json
import sys
import time

import numpy as np

SHAPES = [
    # (name, N scanned rows, M budget, live shares: the first is timed in
    # every form, the others in the payload sort alone — does the sort
    # look at the data?)
    ("q3", 4 * 1_000_448, 1 << 20, (0.01, 0.2)),
    ("small_budget", 6 * 1_000_448, 1 << 15, (0.002,)),
    # the budgets q3 and q10 run under once their shapes have reported
    # their survivors (PR 34): does a probe still cost what it did?
    ("q3_observed", 4 * 1_000_448, 1 << 16, (0.0076,)),
    ("q10_observed", 8 * 1_000_448, 1 << 18, (0.0144,)),
]
SPELLINGS = (("stable", False), ("unique", True))
N_COLS = 5            # q3: three int32 key columns, two float32 values


def _ms(fn, args):
    import jax
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(4):
            r = fn(*args)
        jax.block_until_ready(r)
        ts.append((time.perf_counter() - t0) / 4)
    return float(np.median(ts)) * 1e3


def measure(n, m, lives, rng, spellings=SPELLINGS):
    import jax
    import jax.numpy as jnp

    live = lives[0]
    mask = jnp.asarray(rng.random(n) < live)
    cols = [jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))
            for _ in range(3)]
    cols += [jnp.asarray(rng.random(n).astype(np.float32))
             for _ in range(N_COLS - 3)]

    def order(mask, unique, cols=()):
        """Survivors first in row order: (row positions, sorted cols)."""
        ridx = jnp.arange(n, dtype=jnp.int32)
        if unique:
            key = ridx + jnp.where(mask, jnp.int32(0), jnp.int32(n))
            key, *rode = jax.lax.sort((key, *cols), num_keys=1,
                                      is_stable=False)
            return jnp.where(key >= n, key - n, key), rode
        dead = jnp.where(mask, jnp.int32(0), jnp.int32(1))
        if cols:
            _, *rode = jax.lax.sort((dead, *cols), num_keys=1,
                                    is_stable=True)
            return None, rode
        _, sidx = jax.lax.sort((dead, ridx), num_keys=1, is_stable=True)
        return sidx, []

    def forms(unique):
        def positions(mask):
            return jax.lax.slice_in_dim(order(mask, unique)[0], 0, m)

        def gather(mask, *cols):
            keep = positions(mask)
            return [c[keep] for c in cols]

        def sort(mask, *cols):
            return [jax.lax.slice_in_dim(s, 0, m)
                    for s in order(mask, unique, cols)[1]]

        def packed(mask, *cols):
            keep = positions(mask)
            rows = jnp.stack([jax.lax.bitcast_convert_type(c, jnp.int32)
                              for c in cols], axis=1)[keep]
            return [jax.lax.bitcast_convert_type(rows[:, i], c.dtype)
                    for i, c in enumerate(cols)]

        return positions, gather, sort, packed

    out = {"n": n, "m": m, "live": live}
    k = min(m, int(np.asarray(mask).sum()))
    want = None
    for tag, unique in spellings:
        positions, *carriers = forms(unique)
        out[f"positions_{tag}_ms"] = _ms(jax.jit(positions), (mask,))
        for form in carriers:
            fn = jax.jit(form)
            try:
                got = [np.asarray(x)[:k] for x in fn(mask, *cols)]
            except Exception as e:  # noqa: BLE001 — the compiler's refusal is the finding
                out[f"{form.__name__}_{tag}_ms"] = f"refused: {e!s:.200}"
                continue
            want = want or got
            out[f"{form.__name__}_{tag}_ms"] = _ms(fn, (mask, *cols))
            out[f"{form.__name__}_{tag}_equal"] = all(
                np.array_equal(g, w) for g, w in zip(got, want))
            if form.__name__ == "sort":
                for other in lives[1:]:
                    out[f"sort_{tag}_live{other}_ms"] = _ms(
                        fn, (jnp.asarray(rng.random(n) < other), *cols))
    # the slope of a payload, from the cheap spelling's 1- and 3-payload sorts
    sort = jax.jit(forms(True)[2])
    for c in (1, 3):
        out[f"sort_unique_{c}_payloads_ms"] = _ms(sort, (mask, *cols[:c]))
    return out


def main():
    import jax
    dev = jax.devices()[0]
    rng = np.random.default_rng(29)
    shrink = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    picked = sys.argv[3].split(",") if len(sys.argv) > 3 else None
    doc = {"platform": dev.platform, "device_kind": dev.device_kind,
           "shapes": {name: measure(
               n // shrink, m // shrink, lives, rng,
               SPELLINGS[1:] if name.endswith("_observed") else SPELLINGS)
               for name, n, m, lives in SHAPES
               if picked is None or name in picked}}
    line = json.dumps(doc, indent=1)
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
