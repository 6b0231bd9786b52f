"""Local dataframe operators vs numpy oracles — hypothesis property tests."""
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.dataframe import ops_local as L
from repro.dataframe import reference as R
from repro.dataframe.table import Table, from_numpy

ints = st.integers(min_value=0, max_value=50)


def _table(keys, vals, capacity=None):
    return from_numpy({"k": np.asarray(keys, np.int32),
                       "v": np.asarray(vals, np.float32)},
                      capacity=capacity)


@settings(max_examples=30, deadline=None)
@given(st.lists(ints, min_size=1, max_size=40), st.integers(0, 20))
def test_sort_matches_numpy(keys, extra_cap):
    vals = np.arange(len(keys), dtype=np.float32)
    t = _table(keys, vals, capacity=len(keys) + extra_cap)
    out = L.sort_by(t, "k")
    got = out.to_numpy()
    ref = R.ref_sort({"k": np.asarray(keys, np.int32), "v": vals}, "k")
    np.testing.assert_array_equal(got["k"], ref["k"])
    # stable: values of equal keys keep order
    np.testing.assert_array_equal(got["v"], ref["v"])


@settings(max_examples=30, deadline=None)
@given(st.lists(ints, min_size=1, max_size=30),
       st.lists(ints, min_size=1, max_size=30))
def test_join_matches_numpy(lk, rk):
    left = {"k": np.asarray(lk, np.int32),
            "v": np.arange(len(lk), dtype=np.float32)}
    right = {"k": np.asarray(rk, np.int32),
             "w": np.arange(len(rk), dtype=np.float32) + 100}
    ref = R.ref_join_inner(left, right, "k")
    lt = from_numpy(left, capacity=len(lk) + 5)
    rt = from_numpy(right, capacity=len(rk) + 3)
    out_cap = max(len(ref["k"]), 1) + 8
    out, overflow = L.join_inner(lt, rt, "k", out_cap)
    assert not bool(overflow)
    got = out.to_numpy()
    assert len(got["k"]) == len(ref["k"])
    a = R.sorted_rows(got)
    b = R.sorted_rows(ref)
    np.testing.assert_allclose(a, b)


@settings(max_examples=30, deadline=None)
@given(st.lists(ints, min_size=1, max_size=40))
def test_groupby_sum_matches_numpy(keys):
    vals = np.random.default_rng(0).normal(size=len(keys)).astype(np.float32)
    data = {"k": np.asarray(keys, np.int32), "v": vals}
    t = from_numpy(data, capacity=len(keys) + 4)
    out = L.groupby_sum(t, "k", ["v"])
    got = out.to_numpy()
    ref = R.ref_groupby_sum(data, "k", ["v"])
    assert len(got["k"]) == len(ref["k"])
    o = np.argsort(got["k"])
    np.testing.assert_array_equal(got["k"][o], ref["k"])
    np.testing.assert_allclose(got["v"][o], ref["v"], atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=40))
def test_filter_compacts_stably(keep):
    n = len(keep)
    data = {"k": np.arange(n, dtype=np.int32),
            "v": np.arange(n, dtype=np.float32)}
    t = from_numpy(data, capacity=n + 3)
    keep_padded = np.concatenate([np.asarray(keep), np.zeros(3, bool)])
    out = L.filter_rows(t, jnp.asarray(keep_padded))
    got = out.to_numpy()
    want = data["k"][np.asarray(keep)]
    np.testing.assert_array_equal(got["k"], want)


def _ordered_join_oracle(left, ln, right, rn, out_capacity):
    """join_inner's output in order: valid left rows in stable key order,
    each followed by its valid right matches in stable key order, cut to
    out_capacity and zero-padded; plus nrows and the overflow flag."""
    lk, rk = left["k"][:ln], right["k"][:rn]
    lo_, ro_ = np.argsort(lk, kind="stable"), np.argsort(rk, kind="stable")
    pairs = [(i, j) for i in lo_ for j in ro_ if rk[j] == lk[i]]
    kept = pairs[:out_capacity]
    out = {}
    for name, src, col in (("k", left, 0), ("v", left, 0), ("w", right, 1)):
        buf = np.zeros(out_capacity, src[name].dtype)
        buf[:len(kept)] = [src[name][p[col]] for p in kept]
        out[name] = buf
    return out, len(kept), len(pairs) > out_capacity


def _ordered_join_case(case, rng):
    """(left, ln, right, rn): padded columns of two tables and their rows."""
    lcap, rcap = 23, 17
    dtype = np.float32 if case == "float_keys" else np.int32
    ln, rn = int(rng.integers(8, lcap - 2)), int(rng.integers(6, rcap - 2))
    if case == "no_matches":
        lkeys = 2 * rng.integers(0, 6, lcap)
        rkeys = 2 * rng.integers(0, 6, rcap) + 1
    elif case == "float_keys":
        # 0.0 and -0.0 are one key
        pool = np.asarray([-1.5, -0.0, 0.0, 2.25, 7.0], np.float32)
        lkeys, rkeys = rng.choice(pool, lcap), rng.choice(pool, rcap)
    else:
        lkeys, rkeys = rng.integers(0, 5, lcap), rng.integers(0, 5, rcap)
    if case == "sentinel_keys":
        top = np.iinfo(np.int32).max
        lkeys[rng.random(lcap) < 0.4] = top
        rkeys[rng.random(rcap) < 0.4] = top
    if case == "empty_left":
        ln = 0
    if case == "empty_right":
        rn = 0
    left = {"k": lkeys.astype(dtype),
            "v": rng.normal(size=lcap).astype(np.float32)}
    right = {"k": rkeys.astype(dtype),
             "w": rng.normal(size=rcap).astype(np.float32)}
    return left, ln, right, rn


@pytest.mark.parametrize("fit", ["below", "at", "above"])
@pytest.mark.parametrize("case", ["duplicates", "no_matches", "empty_left",
                                  "empty_right", "sentinel_keys",
                                  "float_keys"])
def test_join_inner_output_in_order_bit_for_bit(case, fit):
    """join_inner's output, nrows and overflow flag equal the ordered
    oracle's exactly, padding included; a short out_capacity keeps the
    same prefix and raises the flag."""
    rng = np.random.default_rng(sum(map(ord, case + fit)))
    for _ in range(4):
        left, ln, right, rn = _ordered_join_case(case, rng)
        lt = Table(columns={k: jnp.asarray(v) for k, v in left.items()},
                   nrows=jnp.int32(ln))
        rt = Table(columns={k: jnp.asarray(v) for k, v in right.items()},
                   nrows=jnp.int32(rn))
        total = _ordered_join_oracle(left, ln, right, rn, 10**4)[1]
        cap = {"below": max(total - 3, 1), "at": max(total, 1),
               "above": total + 6}[fit]
        want, nrows, overflow = _ordered_join_oracle(left, ln, right, rn, cap)
        out, got_overflow = L.join_inner(lt, rt, "k", cap)
        assert sorted(out.columns) == sorted(want)
        for name, col in want.items():
            got = np.asarray(out.columns[name])
            assert got.dtype == col.dtype, name
            np.testing.assert_array_equal(got.view(np.uint32),
                                          col.view(np.uint32), err_msg=name)
        assert int(out.nrows) == nrows
        assert bool(got_overflow) == overflow
        assert overflow == (fit == "below" and total > 1)


def test_join_overflow_flag():
    left = {"k": np.zeros(10, np.int32), "v": np.arange(10, dtype=np.float32)}
    right = {"k": np.zeros(10, np.int32), "w": np.arange(10, dtype=np.float32)}
    lt = from_numpy(left)
    rt = from_numpy(right)
    out, overflow = L.join_inner(lt, rt, "k", out_capacity=16)  # needs 100
    assert bool(overflow)


def test_concat():
    a = from_numpy({"k": np.asarray([1, 2], np.int32)}, capacity=4)
    b = from_numpy({"k": np.asarray([3, 4, 5], np.int32)}, capacity=5)
    out = L.concat(a, b, capacity=8)
    np.testing.assert_array_equal(out.to_numpy()["k"], [1, 2, 3, 4, 5])


def test_to_numpy_on_distributed_table_delegates_to_collect():
    """A distributed Table carries a per-rank nrows VECTOR and rank-major
    padded columns; to_numpy must strip each rank's padding (collect_table
    semantics) instead of crashing on int(vector)."""
    # 2 ranks, capacity 3 each: rank0 holds [1, 2], rank1 holds [5]
    t = Table(columns={"k": jnp.asarray([1, 2, 0, 5, 0, 0], jnp.int32)},
              nrows=jnp.asarray([2, 1], jnp.int32))
    np.testing.assert_array_equal(t.to_numpy()["k"], [1, 2, 5])
    # the scalar (local) path is unchanged
    local = from_numpy({"k": np.asarray([7, 8], np.int32)}, capacity=4)
    np.testing.assert_array_equal(local.to_numpy()["k"], [7, 8])
