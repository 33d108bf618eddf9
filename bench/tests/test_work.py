"""The UDA work counters against counts made by hand."""
from bench.harness.spec import load_module
from conftest import ROOT


def test_cumulants_by_hand():
    w = load_module(ROOT, "work", "cumulants")
    # 2 orders: (2*1+3) + (2*2+3) = 12 ops a tuple; 10 tuples.
    assert w.ops_bytes(rows=10, groups=3, orders=2, grouped=True) == (
        120.0, 10 * 12 + 3 * 2 * 4)
    # 8 orders: sum_j (2j + 3) = 72 + 24 = 96; scalar reads p and v only.
    assert w.ops_bytes(rows=1000, groups=1, orders=8, grouped=False) == (
        96000.0, 8000.0 + 32.0)


def test_no_work_no_ops():
    w = load_module(ROOT, "work", "cumulants")
    assert w.ops_bytes(rows=0, groups=0, orders=8, grouped=True) == (0.0,
                                                                      0.0)
