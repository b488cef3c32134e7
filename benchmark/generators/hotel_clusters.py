"""A hotel-search log at the shape of the Kaggle "Expedia Hotel
Recommendations" ``train.csv``: 22 feature columns (every column but
``user_id`` and the target), the target ``hotel_cluster`` one of 100
classes.

**One table, and a seed that shuffles its rows.**  The table is drawn
from ``TABLE_SEED``, the same for every ``--seed``, as the source's table
is one table; ``--seed`` draws the order in which its rows arrive (a
permutation), as for the Allstate cells (PERF.md section 6: there
a trainer's rate follows the table it is handed).

``make(seed, config)`` reads the configuration's ``table``:

``columns``      the 22 column names in the source's order.
``categorical``  the names declared categorical (their values are small
                 non-negative integers, each under 256 of them).
``cardinality``  ``{name: number of values}`` of every ID column, drawn
                 Zipf(1.0) (value ``j`` with probability proportional to
                 ``1 / (j + 1)``) unless a parent fixes it: a destination
                 fixes its market (``destination // per_market``), a
                 market its country, a country its continent, a site its
                 continent of sale, a user's country the block of regions
                 its region is drawn from.
``missing``      ``{name: share}``: the share of rows whose value is NaN
                 (only ``orig_destination_distance``).
``signal``       the shares of rows whose class is planted: ``market``
                 (the class the row's hotel market prefers), ``country``
                 (its hotel country's), ``distance`` (a class from its
                 hotel continent and its distance's octave, or from its
                 continent alone where the distance is missing); the rest
                 draw a class uniformly.  The preferences are drawn once
                 from the table seed.

The dates are day numbers (``date_time`` within the source's two years,
the check-in a geometric lead after it, the check-out one to fourteen
nights later).  Everything is numpy on the host, vectorised over rows;
the same seed gives the same bytes.  The matrix is float32 ``(rows,
22)``: 0.41 GB at 4,708,787 rows.
"""

from __future__ import annotations

import numpy as np

TABLE_SEED = 20261017


def zipf(rng, width: int, n: int) -> np.ndarray:
    """``n`` draws of ``0 .. width - 1``, value ``j`` with probability
    proportional to ``1 / (j + 1)``."""
    p = 1.0 / np.arange(1, width + 1, dtype=np.float64)
    return rng.choice(width, size=n, p=p / p.sum()).astype(np.int64)


def _table(rows: int, table: dict):
    rng = np.random.default_rng(TABLE_SEED)
    card = table["cardinality"]
    cols = {}

    # where the hotel is: destination -> market -> country -> continent
    dest = zipf(rng, card["srch_destination_id"], rows)
    per_market = -(-card["srch_destination_id"] // card["hotel_market"])
    market = np.minimum(dest // per_market, card["hotel_market"] - 1)
    country_of_market = zipf(rng, card["hotel_country"], card["hotel_market"])
    country = country_of_market[market]
    continent_of_country = rng.integers(0, card["hotel_continent"],
                                        card["hotel_country"])
    continent = continent_of_country[country]
    dtype_of_dest = zipf(rng, card["srch_destination_type_id"],
                         card["srch_destination_id"])
    cols.update(srch_destination_id=dest, hotel_market=market,
                hotel_country=country, hotel_continent=continent,
                srch_destination_type_id=dtype_of_dest[dest])

    # who searches: site -> continent of sale; user country -> region
    site = zipf(rng, card["site_name"], rows)
    posa_of_site = rng.integers(0, card["posa_continent"], card["site_name"])
    ucountry = zipf(rng, card["user_location_country"], rows)
    region_base = rng.integers(0, card["user_location_region"],
                               card["user_location_country"])
    region = (region_base[ucountry] + zipf(rng, 40, rows)) \
        % card["user_location_region"]
    cols.update(site_name=site, posa_continent=posa_of_site[site],
                user_location_country=ucountry, user_location_region=region,
                user_location_city=zipf(rng, card["user_location_city"],
                                        rows),
                channel=zipf(rng, card["channel"], rows))

    # the search itself
    day = rng.integers(0, 730, rows)
    lead = rng.geometric(1.0 / 40.0, rows) - 1
    nights = rng.integers(1, 15, rows)
    cols.update(date_time=day, srch_ci=day + lead,
                srch_co=day + lead + nights,
                is_mobile=rng.random(rows) < 0.13,
                is_package=rng.random(rows) < 0.25,
                srch_adults_cnt=rng.choice(5, rows, p=[0.01, 0.21, 0.66,
                                                      0.06, 0.06]),
                srch_children_cnt=rng.choice(4, rows, p=[0.79, 0.11, 0.08,
                                                        0.02]),
                srch_rm_cnt=1 + rng.choice(3, rows, p=[0.92, 0.06, 0.02]),
                is_booking=rng.random(rows) < 0.08,
                cnt=rng.geometric(0.6, rows))

    # the class: planted from the market, the country, the distance
    k = int(table["num_class"])
    sig = table["signal"]
    pref_market = rng.integers(0, k, card["hotel_market"])
    pref_country = rng.integers(0, k, card["hotel_country"])
    u = rng.random(rows)
    y = rng.integers(0, k, rows)
    lo = 0.0
    for share, cls in ((sig["market"], pref_market[market]),
                       (sig["country"], pref_country[country])):
        pick = (u >= lo) & (u < lo + share)
        y[pick] = cls[pick]
        lo += share
    by_distance = (u >= lo) & (u < lo + sig["distance"])
    gone = rng.random(rows) < table["missing"]["orig_destination_distance"]
    # a distance (miles, log-uniform) whose octave says the class within
    # the continent's band of 14
    dist = np.exp2(rng.uniform(0.0, 13.0, rows))
    planted = by_distance & ~gone
    octave = np.minimum(np.log2(dist).astype(np.int64), 12)
    y[planted] = (continent[planted] * 14 + octave[planted]) % k
    lone = by_distance & gone
    y[lone] = (continent[lone] * 14 + 13) % k
    dist[gone] = np.nan
    cols["orig_destination_distance"] = dist

    x = np.empty((rows, len(table["columns"])), np.float32)
    for j, name in enumerate(table["columns"]):
        x[:, j] = cols[name]
    return x, y.astype(np.float32)


def make(seed: int, config: dict):
    """``(x, y)``: float32 ``(rows, 22)`` with NaN where a distance is
    missing, and float32 class labels ``0 .. num_class - 1``; the rows of
    the one table in the order ``seed`` draws."""
    x, y = _table(int(config["rows"]), config["table"])
    order = np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x407E1]).permutation(
        x.shape[0])
    return x[order], y[order]


def describe(x, y) -> dict:
    counts = np.bincount(y.astype(np.int64))
    return {"rows": int(x.shape[0]), "columns": int(x.shape[1]),
            "classes": int((counts > 0).sum()),
            "largest_class_share": float(counts.max() / counts.sum()),
            "missing_share": float(np.isnan(x).any(axis=1).mean())}
