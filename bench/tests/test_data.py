"""The generator follows TPC-H clause 4.2.3 and is fixed by its seed."""
import numpy as np
import pytest

from bench.data import tpch_gen as G

SIZES = dict(n_orders=7 * 300 + 3, n_parts=2000, n_suppliers=100,
             n_customers=300)


@pytest.fixture(scope="module")
def db():
    return G.generate(seed=2 ** 31 + 5, price_unit_cents=1000, **SIZES)


def test_same_seed_same_tables(db):
    again = G.generate(seed=2 ** 31 + 5, price_unit_cents=1000, **SIZES)
    for name, t in db.items():
        for col, v in t.items():
            np.testing.assert_array_equal(v, again[name][col],
                                          err_msg=f"{name}.{col}")
    other = G.generate(seed=6, price_unit_cents=1000, **SIZES)
    assert not np.array_equal(other["lineitem"]["p"][:100],
                              db["lineitem"]["p"][:100])


def test_lines_balanced_one_to_seven(db):
    li, n = db["lineitem"], SIZES["n_orders"]
    counts = np.bincount(li["l_orderkey"], minlength=n)
    assert counts.min() == 1 and counts.max() == 7
    assert np.array_equal(np.bincount(counts)[1:], [300, 300, 300, 303, 300,
                                                    300, 300])
    assert li["p"].size == 4 * n


def test_dates_follow_the_order(db):
    li, o = db["lineitem"], db["orders"]
    assert o["o_orderdate"].min() >= G.STARTDATE
    assert o["o_orderdate"].max() <= G.ENDDATE - 151
    lag = li["l_shipdate"] - o["o_orderdate"][li["l_orderkey"]]
    assert lag.min() == 1 and lag.max() == 121
    shipped = li["l_shipdate"] > G.CURRENTDATE
    assert np.array_equal(li["l_linestatus"], shipped.astype(int))
    # N exactly where not yet received, which includes every open line
    assert np.all(li["l_returnflag"][shipped] == 1)
    assert set(np.unique(li["l_returnflag"])) == {0, 1, 2}


def test_keys_and_suppliers(db):
    li, ps, o = db["lineitem"], db["partsupp"], db["orders"]
    assert np.all((o["o_custkey"] + 1) % 3 != 0)
    assert o["o_custkey"].max() < SIZES["n_customers"]
    sup = ps["ps_suppkey"].reshape(-1, 4)
    assert all(len(set(r)) == 4 for r in sup)
    with pytest.raises(ValueError):
        G.generate(n_orders=7, n_parts=500, n_suppliers=40, n_customers=9)
    assert np.array_equal(ps["ps_partkey"][li["l_pskey"]], li["l_partkey"])
    assert np.array_equal(ps["ps_suppkey"][li["l_pskey"]], li["l_suppkey"])
    assert li["l_quantity"].min() == 1 and li["l_quantity"].max() == 50
    assert li["l_discount"].min() == 0 and li["l_discount"].max() == 10


def test_prices_by_the_formula(db):
    li = db["lineitem"]
    # part 0 (key 1): 90000 + 0 + 100 = 90,100 cents; part 9999: 90000 +
    # 1000 + 0 = 91,000 cents
    assert list(G.retail_price_cents([0, 9999])) == [90100, 91000]
    cents = li["l_quantity"] * G.retail_price_cents(li["l_partkey"])
    assert np.all(np.abs(li["l_extendedprice"] * 1000 - cents) <= 500)
    # TPC-H's first supplier of part 1 is supplier 2 (1-based)
    assert G.part_suppliers(0, 0, 10000) == 1
