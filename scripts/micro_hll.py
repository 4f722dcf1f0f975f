#!/usr/bin/env python
"""The HLL register stage alone, on the backend JAX gives: the per-(group,
register) maxima of N rows' rho over ``(n_keys + 1) * 2^log2m`` slots.

    python scripts/micro_hll.py [OUT.json [SHRINK [SHAPE ...]]]

(``SHRINK`` divides every N, for a rehearsal off the chip; ``SHAPE``
names of ``SHAPES`` run those alone.)

Every form is fed the same ``(key, mask, values)`` and starts from the same
hash (``ops.hll._rho_and_slot``); host clock around ``block_until_ready``,
the median of 5 rounds of 4 calls; ms:

- ``scatter``: one ``segment_max`` of every row (``ops.hll``'s scatter form);
- ``sort_search``: ``slot * 2^b + rho`` as ONE int32 key, a one-operand
  unstable sort, the run ends of the live slots by a binary search on the
  slot boundaries (``ops.hll``'s sort form);
- ``sort_search_unrolled``: the same with the search's rounds unrolled;
- ``sort_compact``: the same sort, then run ends as PR 27 took them: an
  ``is_last`` mask and a second, compacting sort that brings the run-last
  elements to a static prefix, from where a slots-wide scatter places them;
- ``sort2``: ``(slot, rho)`` as a two-key sort and the search on the slot
  operand — the form for a packed key past 31 bits;
- ``sort_only`` / ``hash_only``: the packed key's sort, and the hash, alone;
- ``sparse``: ``ops.hll``'s sparse form (PR 35) — rows sorted by (group,
  register, rho), three prefix sums, the group boundaries by a search over
  the groups, the estimate on the device: int32 ``[n_keys]`` out, no
  ``[n_keys, m]`` block; ``sparse_sort_only`` its two-operand sort alone.
  Where the dense forms ran, its integer totals are checked against
  their registers (``sparse_equal``). A shape whose dense block passes
  1 GiB runs the sparse form alone.

``ops.hll.register_form`` rests on these numbers (PERF.md, PR 31, PR 35).
"""

import json
import os
import sys

import numpy as np

from micro_compact import _ms            # the sibling script's clock

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

SHAPES = [
    # (name, N rows, share of rows unmasked, n_keys, log2m)
    ("acd", 8 * 1_000_448, 0.75, 7, 11),     # s_pad 8 x padded_rows
    ("acd_6seg", 6 * 1_000_448, 1.0, 7, 11),
    ("mid", 1 << 20, 0.5, 7, 11),
    ("small", 1 << 16, 0.5, 7, 11),          # a [compact_m]-wide input
    ("acd_p14", 8 * 1_000_448, 0.75, 7, 14),
    ("k1000", 8 * 1_000_448, 0.75, 1000, 11),
    ("supplier_p16", 8 * 1_000_448, 0.75, 10_000, 16),   # sparse alone
]


def measure(n, live, n_keys, log2m, rng):
    import jax
    import jax.numpy as jnp
    from spark_druid_olap_tpu.ops import hll as H

    m = 1 << log2m
    bits = H._rho_bits(log2m)
    live_slots = n_keys * m
    key = jnp.asarray(rng.integers(0, n_keys, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < live)
    values = jnp.asarray(rng.integers(0, 200_000, n).astype(np.int32))

    def prep(key, mask, values):
        return H._rho_and_slot(key, mask, values, n_keys, log2m)

    def scatter(key, mask, values):
        return H._registers_scatter(*prep(key, mask, values), n_keys, m)

    def sort_search(key, mask, values):
        return H._registers_sort(*prep(key, mask, values), n_keys, m, bits)

    def sort_search_unrolled(key, mask, values):
        rho, fused = prep(key, mask, values)
        packed = jax.lax.sort((fused << bits) | rho, is_stable=False)
        slots = jnp.arange(live_slots, dtype=jnp.int32)
        ends = jnp.searchsorted(packed, (slots + 1) << bits, side="left",
                                method="scan_unrolled")
        last = packed[jnp.maximum(ends - 1, 0)]
        return jnp.where((ends > 0) & (last >> bits == slots),
                         last & ((1 << bits) - 1), 0)

    def sort_compact(key, mask, values):
        rho, fused = prep(key, mask, values)
        packed = jax.lax.sort((fused << bits) | rho, is_stable=False)
        slot = packed >> bits
        nxt = jnp.concatenate([slot[1:], jnp.full((1,), -1, jnp.int32)])
        is_last = (slot != nxt) & (slot < live_slots)
        big = jnp.int32(np.iinfo(np.int32).max)
        # the run-last elements are distinct: no stability needed
        k = min(live_slots, n)
        heads = jax.lax.slice_in_dim(
            jax.lax.sort(jnp.where(is_last, packed, big), is_stable=False),
            0, k)
        regs = jnp.zeros((live_slots,), jnp.int32)
        return regs.at[jnp.where(heads == big, live_slots, heads >> bits)] \
            .max(heads & ((1 << bits) - 1), mode="drop")

    def sort2(key, mask, values):
        rho, fused = prep(key, mask, values)
        s_slot, s_rho = jax.lax.sort((fused, rho), num_keys=2,
                                     is_stable=False)
        slots = jnp.arange(live_slots, dtype=jnp.int32)
        ends = jnp.searchsorted(s_slot, slots + 1, side="left")
        at = jnp.maximum(ends - 1, 0)
        return jnp.where((ends > 0) & (s_slot[at] == slots), s_rho[at], 0)

    def sort_only(key, mask, values):
        rho, fused = prep(key, mask, values)
        return jax.lax.sort((fused << bits) | rho, is_stable=False)

    def hash_only(key, mask, values):
        rho, fused = prep(key, mask, values)
        return (fused << bits) | rho

    def sparse(key, mask, values):
        return H.hll_estimates(key, mask, values, n_keys, log2m)

    def sparse_sort_only(key, mask, values):
        group = jnp.where(mask, key, n_keys)
        return jax.lax.sort(
            (group, H.packed_registers(values, mask, log2m)), num_keys=2,
            is_stable=False)

    out = {"n": n, "live": live, "n_keys": n_keys, "log2m": log2m,
           "live_slots": live_slots}
    args = (key, mask, values)
    want = None
    dense = live_slots * 4 <= 1 << 30
    for form in (scatter, sort_search, sort_search_unrolled, sort_compact,
                 sort2) if dense else ():
        fn = jax.jit(form)
        try:
            got = np.asarray(fn(*args))
        except Exception as e:  # noqa: BLE001 — the compiler's refusal is the finding
            out[f"{form.__name__}_ms"] = f"refused: {e!s:.200}"
            continue
        want = got if want is None else want
        out[f"{form.__name__}_ms"] = _ms(fn, args)
        out[f"{form.__name__}_equal"] = bool(np.array_equal(got, want))
    for form in (sort_only, hash_only) if dense \
            else (hash_only,):
        out[f"{form.__name__}_ms"] = _ms(jax.jit(form), args)
    for form in (sparse, sparse_sort_only):
        out[f"{form.__name__}_ms"] = _ms(jax.jit(form), args)
    if want is not None:
        sums = jax.jit(lambda *a: H.hll_sums(*a, n_keys, log2m))(*args)
        top = 32 - log2m + 1
        regs = want.reshape(n_keys, m).astype(np.int64)
        out["sparse_equal"] = bool(
            np.array_equal(np.asarray(sums[0]).view(np.uint32),
                           np.where(regs > 0, 1 << (top - regs), 0).sum(1))
            and np.array_equal(np.asarray(sums[1]), (regs > 0).sum(1)))
    if not dense:
        return out
    # what the numbers imply for the unit costs register_form reads
    rounds = max(1, int(n - 1).bit_length())
    sort_ms = out["sort_only_ms"] - out["hash_only_ms"]
    # the sparse form's cost a row in one-operand sorts of that row
    # (ops.hll._SPARSE_ROW_SORTS)
    out["sparse_row_sorts"] = (out["sparse_ms"] - out["hash_only_ms"]) \
        / sort_ms
    out["implied"] = {
        "sort_ns_per_row": sort_ms * 1e6 / n,
        "probe_ns": (out["sort_search_ms"] - out["sort_only_ms"]) * 1e6
        / (live_slots * rounds),
        "scatter_ns_per_update":
            (out["scatter_ms"] - out["hash_only_ms"]) * 1e6 / n,
    }
    return out


def main():
    import jax
    dev = jax.devices()[0]
    rng = np.random.default_rng(31)
    shrink = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    only = sys.argv[3:]                  # shape names; none: every shape
    doc = {"platform": dev.platform, "device_kind": dev.device_kind,
           "shapes": {name: measure(max(n // shrink, 64), live, n_keys,
                                    log2m, rng)
                      for name, n, live, n_keys, log2m in SHAPES
                      if not only or name in only}}
    line = json.dumps(doc, indent=1)
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
