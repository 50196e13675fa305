"""The generator is deterministic in the seed and makes the 23 published
columns; the reference answers as the definitions say."""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

from benchmark.tables import store_sales as ss

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = [
    "ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk", "ss_customer_sk",
    "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk", "ss_store_sk", "ss_promo_sk",
    "ss_ticket_number", "ss_quantity", "ss_wholesale_cost", "ss_list_price",
    "ss_sales_price", "ss_ext_discount_amt", "ss_ext_sales_price",
    "ss_ext_wholesale_cost", "ss_ext_list_price", "ss_ext_tax",
    "ss_coupon_amt", "ss_net_paid", "ss_net_paid_inc_tax", "ss_net_profit"]


def params(rows=30_000):
    with open(os.path.join(HERE, "configs", "store_sales_sf10.json")) as f:
        return dict(json.load(f)["table"], rows=rows)


def test_published_names_and_types():
    schema = ss.arrow_schema()
    assert schema.names == PUBLISHED
    for f in schema:
        if f.name in PUBLISHED[11:]:
            assert f.type == pa.decimal128(7, 2)
        elif f.name == "ss_ticket_number":
            assert f.type == pa.int64()
        else:
            assert f.type == pa.int32()
    assert not schema.field("ss_item_sk").nullable
    assert not schema.field("ss_ticket_number").nullable


def test_real_size_and_domains_are_sf10():
    p = params(rows=None)
    with open(os.path.join(HERE, "configs", "store_sales_sf10.json")) as f:
        assert json.load(f)["table"]["rows"] == 28_800_991
    assert p["domains"]["item"] == 102_000 and p["domains"]["store"] == 102
    assert p["domains"]["customer"] == 500_000 and p["domains"]["date"][1] == 1823


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_rows_other_seed_other_rows(seed):
    a, b = ss.Generator(params(), seed).base(), ss.Generator(params(), seed).base()
    for n in ss.NAMES:
        assert np.array_equal(a.lanes[n], b.lanes[n])
    c = ss.Generator(params(), seed + 1).base()
    assert not np.array_equal(a.lanes["ss_net_paid"], c.lanes["ss_net_paid"])
    assert len(a) == len(c) == 30_000


def test_key_unique_never_null_dates_in_ticket_order():
    g = ss.Generator(params(), 3)
    base = g.base()
    key = base.packed_key()
    assert len(np.unique(key)) == len(key)
    assert (base.lanes["ss_item_sk"] != ss.NULL).all()
    assert (np.diff(base.lanes["ss_ticket_number"]) >= 0).all()
    d = base.lanes["ss_sold_date_sk"]
    assert (np.diff(d[d != ss.NULL]) >= 0).all()
    share = (d == ss.NULL).mean()
    assert 0.03 < share < 0.06
    src = g.upsert_source(base, 0, 2000, 0.5)
    sk = src.packed_key()
    assert len(np.unique(sk)) == 2000
    assert np.isin(sk, key).sum() == 1000
    again = ss.Generator(params(), 3)
    again.base()
    assert np.array_equal(again.upsert_source(base, 0, 2000, 0.5).packed_key(), sk)


def test_arrow_round_trip_keeps_cents_and_nulls():
    base = ss.Generator(params(5000), 11).base()
    t = ss.to_arrow(base)
    assert t.schema == ss.arrow_schema()
    assert ss.diff_rows(t, base) == {
        "rows_missing": 0, "rows_extra": 0, "cells_wrong": 0}
    profit = base.lanes["ss_net_profit"]
    i = int(np.flatnonzero((profit != ss.NULL) & (profit < 0))[0])
    assert int(t.column("ss_net_profit")[i].as_py() * 100) == profit[i]


def test_reference_upsert_and_filter_by_hand():
    base = ss.Generator(params(4000), 5).base()
    g = ss.Generator(params(4000), 5)
    g.base()
    src = g.upsert_source(base, 0, 400, 0.5)
    want, counts = ss.ref_upsert([base, src])
    assert counts == [(200, 200)] and len(want) == 4200
    # by hand: a dict keyed on the primary key, the last write wins
    table = {}
    for rows in (base, src):
        for i, k in enumerate(rows.packed_key().tolist()):
            table[k] = rows.lanes["ss_net_paid"][i]
    got = dict(zip(want.packed_key().tolist(), want.lanes["ss_net_paid"]))
    assert got == table
    terms = [("ss_quantity", ">=", 21), ("ss_quantity", "<=", 40)]
    out = ss.ref_filter(base, terms, ["ss_item_sk", "ss_ticket_number"])
    q = base.lanes["ss_quantity"]
    assert len(out) == int(((q >= 21) & (q <= 40)).sum())


def test_diff_counts_what_differs():
    base = ss.Generator(params(3000), 2).base()
    t = ss.to_arrow(base.slice(0, 2990))
    assert ss.diff_rows(t, base)["rows_missing"] == 10
    assert ss.diff_rows(ss.to_arrow(base), base.slice(0, 2990))["rows_extra"] == 10
    base = base.take(np.arange(len(base)))  # to_arrow shares int lanes
    base.lanes["ss_quantity"][5] += 1
    d = ss.diff_rows(t, base)
    assert d["cells_wrong"] == 1 and d["rows_missing"] == 10
