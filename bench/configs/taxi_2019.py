"""Generator of the ``taxi_2019`` configuration: NYC yellow-cab trips.

Every run's table has the same number of trips on each day, whatever the
seed, so every statement's scan hands the device the same number of rows
and a new seed finds its programs in the compile cache.  The seed draws
the zones, the passenger counts and the rest of each trip record: every
field of the TLC 2019 yellow-cab record, codes as small integers and
dollar amounts as float32.
"""
from __future__ import annotations

import datetime as dt
from typing import Dict, Mapping

import numpy as np

EPOCH = dt.date(1970, 1, 1)


def day_counts(rows: int, days: int) -> np.ndarray:
    """Trips on each day: ``rows // days``, and one more on ``rows % days``
    days spread evenly over the range."""
    extra = rows % days
    d = np.arange(days + 1, dtype=np.int64)
    steps = np.diff(d * extra // days)
    return (rows // days + steps).astype(np.int64)


def zipf_probabilities(n: int, exponent: float) -> np.ndarray:
    weights = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    return weights / weights.sum()


def _zones(rng: np.random.Generator, n: int, order: np.ndarray,
           probs: np.ndarray) -> np.ndarray:
    ranks = np.searchsorted(np.cumsum(probs), rng.random(n), side="right")
    return order[np.minimum(ranks, len(order) - 1)]


def _pick(rng: np.random.Generator, n: int, mix, dtype) -> np.ndarray:
    """``n`` draws from ``mix``, a list of ``[value, probability]``."""
    values, weights = zip(*mix)
    cdf = np.cumsum(np.asarray(weights, np.float64))
    u = rng.random(n, np.float32)
    idx = np.zeros(n, np.int8)
    for edge in (cdf / cdf[-1])[:-1]:
        idx += u >= edge
    return np.asarray(values, dtype)[idx]


def _record(config: Mapping, rng: np.random.Generator, pickup_at: np.ndarray,
            passengers: np.ndarray) -> Dict[str, np.ndarray]:
    """The trip record's other fields, in the data dictionary's order."""
    n = len(pickup_at)
    f = config["fields"]
    distance = np.round(rng.lognormal(np.log(f["trip_distance_median_mi"]),
                                      f["trip_distance_sigma"], n), 2).astype(np.float32)
    fare = np.round(2 * (f["fare_base"] + f["fare_per_mi"] * distance)) / 2
    extra = _pick(rng, n, f["extra_mix"], np.float32)
    payment = _pick(rng, n, f["payment_type_mix"], np.int8)
    tip = np.where(payment == 1, np.round(fare * rng.uniform(0.1, 0.3, n), 2), 0.0)
    tolls = np.where(rng.random(n, np.float32) < f["toll_share"], f["toll"], 0.0)
    congestion = np.where(rng.random(n, np.float32) < f["congestion_share"],
                          f["congestion_surcharge"], 0.0)
    total = fare + extra + f["mta_tax"] + tip + tolls + f["improvement_surcharge"] + congestion
    return {
        "vendor_id": _pick(rng, n, f["vendor_id_mix"], np.int8),
        "dropoff_at": (pickup_at + (rng.random(n, np.float32) < f["past_midnight_share"])
                       ).astype(np.int32),
        "passenger_count": passengers,
        "trip_distance": distance,
        "ratecode_id": _pick(rng, n, f["ratecode_id_mix"], np.int8),
        "store_and_fwd_flag": (rng.random(n, np.float32) < f["store_and_fwd_share"]
                               ).astype(np.int8),
        "payment_type": payment,
        "fare_amount": fare.astype(np.float32),
        "extra": extra,
        "mta_tax": np.full(n, f["mta_tax"], np.float32),
        "tip_amount": tip.astype(np.float32),
        "tolls_amount": tolls.astype(np.float32),
        "improvement_surcharge": np.full(n, f["improvement_surcharge"], np.float32),
        "total_amount": total.astype(np.float32),
        "congestion_surcharge": congestion.astype(np.float32),
    }


def generate(config: Mapping, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """The ``taxi_table`` of ``config``, drawn from ``seed``."""
    rows, days = int(config["rows"]), int(config["days"])
    zones = int(config["zones"])
    first = (dt.date.fromisoformat(config["first_day"]) - EPOCH).days
    counts = day_counts(rows, days)
    pickup_at = np.repeat(np.arange(first, first + days, dtype=np.int32), counts)

    probs = zipf_probabilities(zones, float(config["zipf_exponent"]))
    ids = np.arange(1, zones + 1, dtype=np.int32)
    pickup_order = np.random.default_rng(config["pickup_zone_order_seed"]).permutation(ids)
    dropoff_order = np.random.default_rng(config["dropoff_zone_order_seed"]).permutation(ids)

    rng = np.random.default_rng(seed)
    values, weights = zip(*config["passenger_count_mix"])
    weights = np.asarray(weights, np.float64)
    passengers = rng.choice(np.asarray(values, np.int32), size=rows,
                            p=weights / weights.sum())
    table = {
        "pickup_at": pickup_at,
        "pickup_location_id": _zones(rng, rows, pickup_order, probs),
        "passenger_count": passengers.astype(np.int32),
        "dropoff_location_id": _zones(rng, rows, dropoff_order, probs),
    }
    table.update(_record(config, rng, pickup_at, table["passenger_count"]))
    return {"taxi_table": {c: table[c] for c in config["tables"]["taxi_table"]["columns"]}}
