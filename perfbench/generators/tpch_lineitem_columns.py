"""TPC-H `LINEITEM` joined to `ORDERS`, as the columns Q1 ("Pricing Summary
Report", clause 2.4.1) reads under user-level DP with the customer as the
privacy unit: (o_custkey, the (l_returnflag, l_linestatus) group, the five
value columns of Q1's aggregates).

Rows follow the data laws of clause 4.2.3, written from memory of the
specification (there is no network here; the configuration's `assumed`
says what was not derived). The seed only changes WHICH rows are drawn,
never how many or over what widths, so two seeds do the same amount of
work.
"""

import numpy as np

DAY0 = np.datetime64("1992-01-01")  # STARTDATE


def days(date):
    return int((np.datetime64(date) - DAY0) / np.timedelta64(1, "D"))


LAST_ORDER_DAY = days("1998-08-02")  # ENDDATE - 151 days
CURRENT_DAY = days("1995-06-17")  # CURRENTDATE
SHIP_CUT_DAY = days("1998-12-01") - 90  # Q1's DELTA = 90, its validation value

# The product of the two columns' domains, in the order the partition ids
# count: return flag {A, N, R} x line status {F, O}. The laws populate A/F,
# N/F, N/O and R/F; A/O and R/O stay empty (a line received by CURRENTDATE
# was shipped before it).
GROUPS = ("A/F", "A/O", "N/F", "N/O", "R/F", "R/O")


def generate(rows, seed, customers, parts):
    """(custkey int32[rows], group int32[rows], values float32[rows, 5]).

    Orders are drawn until their lines number exactly `rows` (the last
    order is cut): o_custkey uniform over the keys of [1, customers] that 3
    does not divide; o_orderdate uniform over STARTDATE .. ENDDATE - 151
    days; 1-7 lines an order. A line: l_quantity 1-50; l_partkey uniform
    over [1, parts] and through it p_retailprice = (90000 + (key/10 mod
    20001) + 100 (key mod 1000)) / 100, l_extendedprice = quantity x
    retail price; l_discount 0.00-0.10 and l_tax 0.00-0.08 in cents;
    l_shipdate = o_orderdate + 1..121 days, l_receiptdate = l_shipdate +
    1..30; l_returnflag R or A (a coin) when received by CURRENTDATE, else
    N; l_linestatus O when shipped after CURRENTDATE, else F.

    `group` is the index into GROUPS, or -1 for the lines Q1's `where
    l_shipdate <= 1998-12-01 - 90 days` leaves out: they carry no
    partition. `values` holds, in this order: l_quantity, l_extendedprice,
    l_extendedprice (1 - l_discount), l_extendedprice (1 - l_discount)
    (1 + l_tax), l_discount."""
    rng = np.random.default_rng(seed)
    # Enough orders that their lines pass `rows` by sixteen standard
    # deviations of the total; the surplus is cut.
    orders = rows // 4 + 4 * int(np.sqrt(rows)) + 16
    lines = rng.integers(1, 8, orders, dtype=np.int32)
    ends = np.cumsum(lines, dtype=np.int64)
    orders = int(np.searchsorted(ends, rows, side="left")) + 1
    lines = lines[:orders]
    lines[-1] -= int(ends[orders - 1] - rows)
    has_orders = customers - customers // 3  # keys 3 does not divide
    nth = rng.integers(0, has_orders, orders, dtype=np.int32)
    custkey = (nth + nth // 2 + 1).astype(np.int32)  # 1, 2, 4, 5, 7, ...
    order_day = rng.integers(0, LAST_ORDER_DAY + 1, orders, dtype=np.int32)
    custkey = np.repeat(custkey, lines)
    # In place from here on: a fresh 60 M-row array costs more to fault in
    # than to fill.
    ship_day = rng.integers(1, 122, rows, dtype=np.int32)
    ship_day += np.repeat(order_day, lines)
    receipt_day = rng.integers(1, 31, rows, dtype=np.int32)
    receipt_day += ship_day
    group = rng.integers(0, 2, rows, dtype=np.int32)  # the coin: A or R
    group *= 2
    np.copyto(group, 1, where=receipt_day > CURRENT_DAY)  # not received: N
    group *= 2
    group += ship_day > CURRENT_DAY  # line status O
    np.copyto(group, -1, where=ship_day > SHIP_CUT_DAY)
    del ship_day, receipt_day
    values = np.empty((rows, 5), dtype=np.float32)
    quantity = rng.integers(1, 51, rows, dtype=np.int32)
    cents = rng.integers(1, parts + 1, rows, dtype=np.int32)  # l_partkey
    tens = cents // 10
    tens %= 20001
    cents %= 1000
    cents *= 100
    cents += tens
    cents += 90000  # p_retailprice in cents
    del tens
    values[:, 0] = quantity
    price = np.multiply(cents, quantity, dtype=np.float64)
    price /= 100.0
    values[:, 1] = price
    del cents, quantity
    percent = rng.integers(0, 11, rows, dtype=np.int32)  # l_discount
    factor = np.divide(percent, 100.0)
    values[:, 4] = factor
    np.subtract(1.0, factor, out=factor)
    price *= factor
    values[:, 2] = price
    percent = rng.integers(0, 9, rows, dtype=np.int32)  # l_tax
    np.divide(percent, 100.0, out=factor)
    factor += 1.0
    price *= factor
    values[:, 3] = price
    return custkey, group, values
