"""HLL registers at register level (ops/hll.py).

The per-(group, register) maxima are taken in one of two forms — a
packed-key sort with a run-end search, or a scatter of every row —
chosen by ``register_form`` from the static shapes and the backend's unit
costs. Both must return the integers a NumPy reference computes, bit for
bit; the CPU's constants always scatter, so the cases pin each form
through the function's cost argument.
"""

import numpy as np
import pytest

import spark_druid_olap_tpu as sdot
from spark_druid_olap_tpu.ops import hll as H

# a row costs nothing to sort / nothing to scatter: pins the form
FORMS = {"sort": H.RegisterCosts(1e-15, 1e-15, 1.0),
         "scatter": H.RegisterCosts(1.0, 1.0, 1e-15)}
M32 = 0xFFFFFFFF


def _fmix32(x):
    x = np.asarray(x).astype(np.int32).view(np.uint32).astype(np.uint64)
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    x ^= x >> 16
    return x


def _unmix32(h):
    """The int32 values whose murmur finalizer is ``h`` (it is a
    bijection on 32 bits)."""
    h = np.asarray(h, np.uint64)
    h ^= h >> 16
    h = (h * pow(0xC2B2AE35, -1, 1 << 32)) & M32
    h ^= (h >> 13) ^ (h >> 26)
    h = (h * pow(0x85EBCA6B, -1, 1 << 32)) & M32
    h ^= h >> 16
    return h.astype(np.uint32).view(np.int32)


def _reference(key, mask, values, n_keys, log2m):
    """{(group, register): max rho} by a Python loop over the live rows."""
    h = _fmix32(values)
    regs = {}
    for k, live, hv in zip(key.tolist(), mask.tolist(), h.tolist()):
        if not live or not 0 <= k < n_keys:
            continue
        w = hv >> log2m
        rho = (32 - log2m) - w.bit_length() + 1
        slot = (k, hv & ((1 << log2m) - 1))
        regs[slot] = max(regs.get(slot, 0), rho)
    return regs


def _rows(n, n_keys, seed, live=0.7, distinct=5000):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_keys, n).astype(np.int32),
            rng.random(n) < live,
            rng.integers(-distinct, distinct, n).astype(np.int32))


def _all_masked():
    key, _, values = _rows(1000, 7, 1)
    return key, np.zeros(1000, bool), values, 7, 11


def _empty_group():
    key, mask, values = _rows(3001, 6, 2)
    key[key == 3] = 4                    # group 3 has no row
    return key, mask, values, 6, 11


def _one_register():
    # every row the same value: one register a group, duplicates throughout
    key, mask, _ = _rows(2000, 7, 3)
    return key, mask, np.full(2000, 12345, np.int32), 7, 11


def _widest_rho(log2m):
    # h < m, so w == 0: rho = 32 - log2m + 1 in every register of group 0,
    # the widest value the packed key's rho field must hold
    m = 1 << log2m
    values = _unmix32(np.arange(m))
    return np.zeros(m, np.int32), np.ones(m, bool), values, 2, log2m


def _sentinel_rows():
    # callers may send masked rows under the sentinel key itself
    key, mask, values = _rows(4097, 7, 4)
    key[~mask] = 7
    return key, np.ones(4097, bool), values, 7, 11


CASES = {
    "k1_m11": lambda: (*_rows(5000, 1, 10), 1, 11),
    "k7_m11": lambda: (*_rows(20011, 7, 11), 7, 11),
    "k1000_m4": lambda: (*_rows(30000, 1000, 12), 1000, 4),
    "k7_m4": lambda: (*_rows(999, 7, 13), 7, 4),
    "k7_m14": lambda: (*_rows(70001, 7, 14, distinct=1 << 30), 7, 14),
    "all_masked": _all_masked,
    "empty_group": _empty_group,
    "one_register": _one_register,
    "duplicates": lambda: (*_rows(10007, 7, 15, distinct=20), 7, 11),
    "widest_rho_m11": lambda: _widest_rho(11),
    "widest_rho_m4": lambda: _widest_rho(4),
    "widest_rho_m14": lambda: _widest_rho(14),
    "sentinel_key_rows": _sentinel_rows,
    "one_row": lambda: (*_rows(1, 7, 16, live=1.0), 7, 11),
    "two_dims": lambda: tuple(
        a.reshape(3, 1001) if i < 3 else a
        for i, a in enumerate((*_rows(3003, 7, 17), 7, 11))),
}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_registers_match_reference(case, form):
    import jax
    key, mask, values, n_keys, log2m = CASES[case]()
    costs = FORMS[form]
    assert H.register_form(key.size, n_keys, log2m, costs) == form
    got = np.asarray(jax.jit(
        lambda k, m, v: H.hll_registers(k, m, v, n_keys, log2m, costs))(
            key, mask, values))
    assert got.shape == (n_keys, 1 << log2m) and got.dtype == np.int32
    want = np.zeros_like(got)
    for (k, r), rho in _reference(key.reshape(-1), mask.reshape(-1),
                                  values.reshape(-1), n_keys,
                                  log2m).items():
        want[k, r] = rho
    np.testing.assert_array_equal(got, want)
    if case.startswith("widest_rho"):
        assert (got[0] == 32 - log2m + 1).all() and not got[1].any()
    if case == "all_masked":
        assert not got.any()
    if case == "empty_group":
        assert not got[3].any() and got[2].any()


def test_packed_key_past_int32_scatters_and_does_not_wrap():
    """(n_keys + 1) * m * 2^b past 2^31: the packed key would wrap, so the
    function scatters whatever the costs say; the last slot that fits
    still sorts."""
    import jax
    sort = FORMS["sort"]
    assert H.register_form(10 ** 6, 32767, 11, sort) == "sort"
    assert H.register_form(10 ** 6, 32768, 11, sort) == "scatter"
    assert H.register_form(10 ** 6, 4095, 14, sort) == "sort"
    n_keys, log2m = 4096, 14
    assert H.register_form(777, n_keys, log2m, sort) == "scatter"
    key, mask, values = _rows(777, n_keys, 18)
    key[:50] = n_keys - 1                # the slots where a wrap would show
    got = np.asarray(jax.jit(
        lambda k, m, v: H.hll_registers(k, m, v, n_keys, log2m, sort))(
            key, mask, values))
    want = _reference(key, mask, values, n_keys, log2m)
    assert int(np.count_nonzero(got)) == len(want)
    for (k, r), rho in want.items():
        assert int(got[k, r]) == rho


def test_rule_picks_the_form():
    """On the v5e's constants the sort wins at ``acd``'s shape and the
    scatter for a small input over the same slots; on the CPU table's
    the scatter always."""
    import jax
    from spark_druid_olap_tpu.utils import config as CF
    v5e = H.RegisterCosts(float(CF.COST_SORT_ROW.default),
                          float(CF.COST_GATHER_PROBE.default),
                          float(CF.COST_SCATTER_UPDATE.default))
    assert H.register_form(8 * 1_000_448, 7, 11, v5e) == "sort"
    assert H.register_form(6 * 1_000_448, 7, 11, v5e) == "sort"
    assert H.register_form(1 << 10, 7, 11, v5e) == "scatter"
    assert H.register_form(1 << 16, 7, 11, v5e) == "scatter"
    assert H.register_form(6 * 1_000_448, 1000, 11, v5e) == "scatter"
    assert jax.default_backend() == "cpu"
    from types import SimpleNamespace as Plan
    eng = sdot.Context().engine
    assert eng._hll_costs([Plan(kind="sum"), Plan(kind="theta")]) is None
    cpu = eng._hll_costs([Plan(kind="sum"), Plan(kind="hll")])
    assert cpu.sort_row_s > 10 * cpu.scatter_s
    for n, k in ((8 * 1_000_448, 7), (1 << 30, 1), (1 << 10, 7)):
        assert H.register_form(n, k, 11, cpu) == "scatter"


ACD = ("select l_shipmode, approx_count_distinct(l_partkey) as parts, "
       "count(*) as n from lineitem group by l_shipmode "
       "order by l_shipmode")
SCATTER_KEY = "sdot.querycostmodel.scatter.seconds.per.update"


def test_acd_answers_alike_in_either_form():
    """``acd`` over TPC-H SF 0.01 through SQL: the same registers, so the
    same estimate to the last digit, and the record says which form ran.
    A ``SET`` of a unit cost re-keys the program (the costs are part of
    every signature), so the flipped form is never served stale."""
    import pandas as pd
    from spark_druid_olap_tpu.tools import tpch
    ctx = sdot.Context()
    tables, _ = tpch.setup_context(ctx, sf=0.01, target_rows=1 << 14)
    runs = {}
    for form, price in (("scatter", 1e-15), ("sort", 1.0),
                        ("scatter", 1e-15)):
        ctx.config.set(SCATTER_KEY, price)
        got = ctx.sql(ACD).to_pandas()
        st = ctx.history.entries()[-1].stats
        assert st["hll_form"] == form, st
        assert st["hll_slots"] == (7 + 1) << 11
        if form in runs:
            assert not st["program"]["built"]
        runs.setdefault(form, (got, st["program"]["sig"]))
    pd.testing.assert_frame_equal(runs["sort"][0], runs["scatter"][0],
                                  check_exact=True)
    assert runs["sort"][1] != runs["scatter"][1]
    li = tables["lineitem"]
    want = li.groupby("l_shipmode")["l_partkey"].nunique()
    got = runs["sort"][0].set_index("l_shipmode")
    assert (got["n"] == li.groupby("l_shipmode").size()).all()
    assert (abs(got["parts"] - want) / want).max() < 0.05
    # a statement without a sketch says nothing of one
    ctx.sql("select count(*) as n from lineitem").to_pandas()
    assert "hll_form" not in ctx.history.entries()[-1].stats
