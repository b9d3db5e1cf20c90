"""The benchmark's own copy of the TPC-H generator (vectorised dbgen-alike).

Copied from ``spark_rapids_tpu/benchmarks/tpch_data.py`` (PR 24) so that a
later PR which edits the program's generator cannot move the yardstick. It
imports nothing of the program. Doubles stand in for decimals as in the
reference's TpchLikeSpark.scala. ``gen_tables(names, scale, seed)`` makes
only the tables a cell's queries touch; every table's stream depends on the
seed alone, so a table is the same whichever others are made beside it.
"""
from __future__ import annotations

import datetime
from typing import Dict

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

_EPOCH = datetime.date(1970, 1, 1)
_D = lambda y, m, d: (datetime.date(y, m, d) - _EPOCH).days  # noqa: E731

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (name, regionkey) — the spec's 25 nations
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
TYPE_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "chartreuse", "chiffon", "chocolate", "coral", "cornflower",
          "cream", "cyan", "dark", "deep", "dim", "dodger", "drab",
          "firebrick", "floral", "forest", "frosted", "gainsboro", "ghost",
          "goldenrod", "green", "grey", "honeydew", "hot", "indian", "ivory",
          "khaki", "lace", "lavender", "lawn", "lemon", "light", "lime",
          "linen", "magenta", "maroon", "medium", "metallic", "midnight"]
_WORDS = ["carefully", "furiously", "quickly", "ironic", "final", "bold",
          "pending", "regular", "express", "silent", "even", "blithely",
          "deposits", "packages", "accounts", "theodolites", "instructions",
          "foxes", "pinto", "beans", "dependencies", "platelets"]

N_SUPP_PER_PART = 4


# row-count floors keep tiny test scales dense enough that every query's
# predicates qualify rows (25 nations need >~100 suppliers for nation-pair
# queries like Q7/Q21 to produce output)
def n_supplier(scale: float) -> int:
    return max(int(10_000 * scale), 100)


def n_customer(scale: float) -> int:
    return max(int(150_000 * scale), 300)


def n_part(scale: float) -> int:
    return max(int(200_000 * scale), 200)


def n_orders(scale: float) -> int:
    return max(int(1_500_000 * scale), 3000)


def _orderdates(scale: float, seed: int) -> "np.ndarray":
    """Order dates drawn from a dedicated stream so gen_orders and
    gen_lineitem_full (ship/commit/receipt = orderdate + offsets) stay
    consistent without materializing each other's tables."""
    rng = np.random.default_rng((seed + 5) * 1_000_003 + 17)
    return rng.integers(_D(1992, 1, 1), _D(1998, 8, 3),
                        n_orders(scale)).astype(np.int32)


def _comment(rng, n, salt_phrase=None, salt_frac=0.02):
    """Random word-soup comments; salt_frac of rows get the two salt words
    embedded in order (with a word between, so only multi-segment LIKEs hit).

    Same draws and same strings as the program's generator, built by one
    ``take`` from the 22**3 possible three-word comments instead of
    ``np.char.add`` over every row (12 s of 22 s for SF1 lineitem)."""
    w = np.array(_WORDS)
    k = len(w)
    i1, i2, i3 = (rng.integers(0, k, n) for _ in range(3))
    soup = pa.array([f"{a} {b} {c}" for a in _WORDS for b in _WORDS
                     for c in _WORDS])
    c = soup.take(pa.array((i1 * k + i2) * k + i3))
    if salt_phrase is not None:
        a, b = salt_phrase
        hit = rng.random(n) < salt_frac
        mid = w[rng.integers(0, k, n)]
        last = w[rng.integers(0, k, n)]
        salted = pa.array([f"{a} {m} {b} {z}" if h else None
                           for h, m, z in zip(hit, mid, last)],
                          type=pa.string())
        c = pc.if_else(pa.array(hit), salted, c)
    return c


def _phone(nationkey):
    code = (10 + nationkey).astype(np.int64)
    return np.char.add(code.astype(str),
                       "-" + np.char.zfill(
                           (nationkey * 7919 % 10_000_000).astype(str), 7))


def gen_region() -> pa.Table:
    return pa.table({
        "r_regionkey": pa.array(np.arange(5, dtype=np.int64)),
        "r_name": pa.array(REGIONS),
        "r_comment": pa.array([f"{r.lower()} region" for r in REGIONS]),
    })


def gen_nation() -> pa.Table:
    return pa.table({
        "n_nationkey": pa.array(np.arange(25, dtype=np.int64)),
        "n_name": pa.array([n for n, _ in NATIONS]),
        "n_regionkey": pa.array(np.array([r for _, r in NATIONS], np.int64)),
        "n_comment": pa.array([f"{n.lower()} nation" for n, _ in NATIONS]),
    })


def gen_supplier(scale: float, seed: int) -> pa.Table:
    n = n_supplier(scale)
    rng = np.random.default_rng(seed + 1)
    keys = np.arange(1, n + 1, dtype=np.int64)
    nationkey = rng.integers(0, 25, n).astype(np.int64)
    return pa.table({
        "s_suppkey": pa.array(keys),
        "s_name": pa.array(np.char.add("Supplier#", np.char.zfill(keys.astype(str), 9))),
        "s_address": pa.array(np.char.add("addr ", keys.astype(str))),
        "s_nationkey": pa.array(nationkey),
        "s_phone": pa.array(_phone(nationkey)),
        "s_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n), 2)),
        "s_comment": pa.array(_comment(rng, n, ("Customer", "Complaints"), 0.05)),
    })


def gen_customer(scale: float, seed: int) -> pa.Table:
    n = n_customer(scale)
    rng = np.random.default_rng(seed + 2)
    keys = np.arange(1, n + 1, dtype=np.int64)
    nationkey = rng.integers(0, 25, n).astype(np.int64)
    seg = np.array(SEGMENTS)
    return pa.table({
        "c_custkey": pa.array(keys),
        "c_name": pa.array(np.char.add("Customer#", np.char.zfill(keys.astype(str), 9))),
        "c_address": pa.array(np.char.add("caddr ", keys.astype(str))),
        "c_nationkey": pa.array(nationkey),
        "c_phone": pa.array(_phone(nationkey)),
        "c_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n), 2)),
        "c_mktsegment": pa.array(seg[rng.integers(0, 5, n)]),
        "c_comment": pa.array(_comment(rng, n)),
    })


def gen_part(scale: float, seed: int) -> pa.Table:
    n = n_part(scale)
    rng = np.random.default_rng(seed + 3)
    keys = np.arange(1, n + 1, dtype=np.int64)
    colors = np.array(COLORS)
    name = np.char.add(np.char.add(colors[rng.integers(0, len(colors), n)], " "),
                       colors[rng.integers(0, len(colors), n)])
    t1 = np.array(TYPE_1)[rng.integers(0, len(TYPE_1), n)]
    t2 = np.array(TYPE_2)[rng.integers(0, len(TYPE_2), n)]
    t3 = np.array(TYPE_3)[rng.integers(0, len(TYPE_3), n)]
    ptype = np.char.add(np.char.add(np.char.add(t1, " "), np.char.add(t2, " ")), t3)
    cont = np.char.add(
        np.char.add(np.array(CONTAINER_1)[rng.integers(0, 5, n)], " "),
        np.array(CONTAINER_2)[rng.integers(0, 8, n)])
    brand = np.char.add("Brand#", (rng.integers(1, 6, n) * 10
                                   + rng.integers(1, 6, n)).astype(str))
    return pa.table({
        "p_partkey": pa.array(keys),
        "p_name": pa.array(name),
        "p_mfgr": pa.array(np.char.add("Manufacturer#", rng.integers(1, 6, n).astype(str))),
        "p_brand": pa.array(brand),
        "p_type": pa.array(ptype),
        "p_size": pa.array(rng.integers(1, 51, n).astype(np.int32)),
        "p_container": pa.array(cont),
        "p_retailprice": pa.array(np.round(900 + (keys % 1000) * 100 / 1000.0
                                           + 100 * (keys % 10), 2)),
        "p_comment": pa.array(_comment(rng, n)),
    })


def _ps_suppkey(partkey, i, n_supp):
    """Deterministic part->supplier map shared by partsupp and lineitem so the
    (l_partkey, l_suppkey) FK into partsupp always holds (dbgen does the same
    with its supplier-distribution formula)."""
    return ((partkey + i * (n_supp // N_SUPP_PER_PART + 1)) % n_supp) + 1


def gen_partsupp(scale: float, seed: int) -> pa.Table:
    np_ = n_part(scale)
    n_supp = n_supplier(scale)
    rng = np.random.default_rng(seed + 4)
    partkey = np.repeat(np.arange(1, np_ + 1, dtype=np.int64), N_SUPP_PER_PART)
    i = np.tile(np.arange(N_SUPP_PER_PART, dtype=np.int64), np_)
    n = partkey.shape[0]
    return pa.table({
        "ps_partkey": pa.array(partkey),
        "ps_suppkey": pa.array(_ps_suppkey(partkey, i, n_supp)),
        "ps_availqty": pa.array(rng.integers(1, 10_000, n).astype(np.int32)),
        "ps_supplycost": pa.array(np.round(rng.uniform(1.0, 1000.0, n), 2)),
        "ps_comment": pa.array(_comment(rng, n)),
    })


def gen_orders(scale: float, seed: int) -> pa.Table:
    n = n_orders(scale)
    n_cust = n_customer(scale)
    rng = np.random.default_rng(seed + 5)
    keys = np.arange(1, n + 1, dtype=np.int64)
    # dbgen gives orders to only 2/3 of customers (custkey % 3 != 0): Q13/Q22
    # depend on orderless customers existing
    cust_pool = np.arange(1, n_cust + 1, dtype=np.int64)
    cust_pool = cust_pool[cust_pool % 3 != 0]
    orderdate = _orderdates(scale, seed)
    # status correlates with age like dbgen output: old orders are fulfilled
    status = np.where(orderdate < _D(1995, 6, 17), "F",
                      np.where(rng.random(n) < 0.05, "P", "O"))
    return pa.table({
        "o_orderkey": pa.array(keys),
        "o_custkey": pa.array(cust_pool[rng.integers(0, cust_pool.shape[0], n)]),
        "o_orderstatus": pa.array(status),
        "o_totalprice": pa.array(np.round(rng.uniform(850.0, 560_000.0, n), 2)),
        "o_orderdate": pa.array(orderdate, type=pa.date32()),
        "o_orderpriority": pa.array(np.array(PRIORITIES)[rng.integers(0, 5, n)]),
        "o_clerk": pa.array(np.char.add("Clerk#", np.char.zfill(
            rng.integers(1, max(n // 1000, 2), n).astype(str), 9))),
        "o_shippriority": pa.array(np.zeros(n, np.int32)),
        "o_comment": pa.array(_comment(rng, n, ("special", "requests"), 0.03)),
    })


def gen_lineitem_full(scale: float, seed: int) -> pa.Table:
    n_ord = n_orders(scale)
    np_ = n_part(scale)
    n_supp = n_supplier(scale)
    rng = np.random.default_rng(seed + 6)
    lines_per = rng.integers(1, 8, n_ord)
    orderkey = np.repeat(np.arange(1, n_ord + 1, dtype=np.int64), lines_per)
    n = orderkey.shape[0]
    linenumber = (np.arange(n, dtype=np.int64)
                  - np.repeat(np.cumsum(lines_per) - lines_per, lines_per) + 1)
    odate = _orderdates(scale, seed)[orderkey - 1]
    shipdate = odate + rng.integers(1, 122, n).astype(np.int32)
    commitdate = odate + rng.integers(30, 91, n).astype(np.int32)
    receiptdate = shipdate + rng.integers(1, 31, n).astype(np.int32)
    partkey = rng.integers(1, np_ + 1, n).astype(np.int64)
    suppkey = _ps_suppkey(partkey, rng.integers(0, N_SUPP_PER_PART, n), n_supp)
    quantity = rng.integers(1, 51, n).astype(np.float64)
    extendedprice = np.round(quantity * rng.uniform(900, 2100, n), 2)
    flags = np.where(receiptdate <= _D(1995, 6, 17),
                     np.where(rng.random(n) < 0.5, "R", "A"), "N")
    return pa.table({
        "l_orderkey": pa.array(orderkey),
        "l_partkey": pa.array(partkey),
        "l_suppkey": pa.array(suppkey),
        "l_linenumber": pa.array(linenumber.astype(np.int32)),
        "l_quantity": pa.array(quantity),
        "l_extendedprice": pa.array(extendedprice),
        "l_discount": pa.array(np.round(rng.uniform(0.0, 0.1, n), 2)),
        "l_tax": pa.array(np.round(rng.uniform(0.0, 0.08, n), 2)),
        "l_returnflag": pa.array(flags),
        "l_linestatus": pa.array(np.where(shipdate > _D(1995, 6, 17), "O", "F")),
        "l_shipdate": pa.array(shipdate, type=pa.date32()),
        "l_commitdate": pa.array(commitdate, type=pa.date32()),
        "l_receiptdate": pa.array(receiptdate, type=pa.date32()),
        "l_shipinstruct": pa.array(np.array(SHIPINSTRUCT)[rng.integers(0, 4, n)]),
        "l_shipmode": pa.array(np.array(SHIPMODES)[rng.integers(0, 7, n)]),
        "l_comment": pa.array(_comment(rng, n)),
    })


GENERATORS = {
    "region": lambda scale, seed: gen_region(),
    "nation": lambda scale, seed: gen_nation(),
    "supplier": gen_supplier,
    "customer": gen_customer,
    "part": gen_part,
    "partsupp": gen_partsupp,
    "orders": gen_orders,
    "lineitem": gen_lineitem_full,
}


def gen_tables(names, scale: float, seed: int) -> Dict[str, pa.Table]:
    """The named tables, each from its own seeded stream."""
    return {name: GENERATORS[name](scale, seed) for name in names}


def gen_all(scale: float = 0.001, seed: int = 0) -> Dict[str, pa.Table]:
    return gen_tables(list(GENERATORS), scale, seed)
