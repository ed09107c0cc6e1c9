"""The NEXmark event stream: one stream of union rows (``event_type`` 0 a
person, 1 an auction, 2 a bid), as Apache Beam's ``nexmark`` generator
defines it and Flink's ``nexmark`` suite reads it.

**Every rule and every value here is from memory of Beam's
``GeneratorConfig``, ``NexmarkConfiguration``, ``AuctionGenerator``,
``BidGenerator`` and ``PriceGenerator``** (no network in this sandbox): a
configuration lists each parameter under its ``assumed`` as such, and a
reader with the source at hand checks them against it.

Event number ``e`` (``first_event_number`` + the stream's index) falls
in period ``e // 50`` at offset ``e % 50``: ``person_proportion`` (1)
persons, then ``auction_proportion`` (3) auctions, then
``bid_proportion`` (46) bids. Person and auction ids grow with the event
number from ``first_person_id`` / ``first_auction_id`` (1,000). With
``newest`` the newest id opened at ``e`` (counted from 0):

- a bid's ``auction`` is, with probability 1 - 1/``hot_auction_ratio``
  (2), the hot one, ``newest // 100 * 100``; else uniform over the newest
  ``in_flight_auctions`` (100) ids and the next 10 (ids not opened yet);
- its ``bidder`` is, with probability 1 - 1/``hot_bidder_ratio`` (4),
  ``newest // 100 * 100 + 1``; else uniform over the newest
  ``active_people`` (1,000) ids and the next 10; an auction's ``seller``
  alike with ``hot_seller_ratio`` (4), the hot one ``newest // 100 * 100``;
- a price is ``round(100 * 10 ** (6 u))``, ``u`` uniform in [0, 1): a
  bid's ``price``, an auction's initial bid (its ``price`` here) and the
  surplus of its ``reserve`` over that; ``category`` is 10 + one of 5;
- ``expires`` is ``dateTime`` + 1 + uniform below twice the time that
  ``in_flight_auctions`` auctions take to open;
- ``dateTime`` = ``base_time_ms`` + ``floor(i * 1000 / event_time_rate)``
  for the stream's event ``i``: at the rates the suites run, thousands of
  events share a millisecond.

A column that the event's type does not have holds 0 (the source's
null). Numeric columns only: a string field of the source (``name``,
``itemName``, ``extra`` ...) is refused by name, not faked.

**Cycles.** The draws ``u`` repeat with the pool's period ``n``; ids and
times run on: cycle ``c`` adds ``c * n * 3 / 50`` to auction ids,
``c * n / 50`` to person ids and ``c * n * 1000 / event_time_rate`` ms to
``dateTime`` and ``expires``. For that to equal the stream generated
directly (same draws, event number ``e``), ``n`` is a multiple of the
period, the two id steps are multiples of 100 (``n`` a multiple of 5,000
at 1 : 3 : 46) and the cycle's span a whole number of ms; anything else
is refused. What still repeats with the draws: a young stream's narrower
ranges (fewer than ``in_flight_auctions`` auctions or ``active_people``
persons yet), which a ``first_event_number`` of 50 x ``active_people``
or more leaves behind, as a Beam sub-generator starts mid-stream.
"""

from __future__ import annotations

import numpy as np

PERSON, AUCTION, BID = 0, 1, 2
HOT_ROUND = 100  # HOT_AUCTION_RATIO, HOT_SELLER_RATIO, HOT_BIDDER_RATIO
ID_LEAD = 10  # AUCTION_ID_LEAD, PERSON_ID_LEAD
FIRST_CATEGORY, CATEGORIES = 10, 5
COLUMNS = ("event_type", "id", "auction", "bidder", "seller", "category",
           "price", "reserve", "expires", "dateTime")
TIME_FIELD = "dateTime"
# the source's defaults, for a configuration to copy (from memory)
SOURCE_DEFAULTS = {
    "person_proportion": 1, "auction_proportion": 3, "bid_proportion": 46,
    "first_person_id": 1000, "first_auction_id": 1000,
    "hot_auction_ratio": 2, "hot_bidder_ratio": 4, "hot_seller_ratio": 4,
    "in_flight_auctions": 100, "active_people": 1000,
    "first_event_number": 0, "base_time_ms": 1436918400000,
}
DTYPES = {"int": np.int32, "long": np.int64, "float": np.float32,
          "double": np.float64}


def _price(u):
    return np.floor(100.0 * 10.0 ** (6.0 * u) + 0.5).astype(np.int64)


def _hot(u, ratio):
    """Beam's ``random.nextInt(ratio) > 0``."""
    return np.floor(u * ratio) > 0


class Pool:
    def __init__(self, seed: int, n: int, cfg) -> None:
        try:
            p = {k: int(cfg[k]) for k in SOURCE_DEFAULTS}
            rate = int(cfg["event_time_rate"])
        except KeyError as e:
            raise ValueError(
                f"generator nexmark: the configuration lacks {e}; it states "
                "every parameter itself (SOURCE_DEFAULTS has the source's)"
            ) from None
        n_p, n_a = p["person_proportion"], p["auction_proportion"]
        period = n_p + n_a + p["bid_proportion"]
        self.n, self.rate, self.base_time = n, rate, p["base_time_ms"]
        step_p, step_a = n // period * n_p, n // period * n_a
        if n % period or step_p % HOT_ROUND or step_a % HOT_ROUND:
            raise ValueError(
                f"generator nexmark: a pool of {n} events cannot cycle: it "
                f"has to be a multiple of the period ({period}) that opens "
                f"a multiple of {HOT_ROUND} persons and auctions")
        if n * 1000 % rate:
            raise ValueError(
                f"generator nexmark: {n} events at {rate} a second of event "
                "time do not span a whole number of ms")
        self.span = n * 1000 // rate
        self.dtypes = {}
        for name, kind in cfg["fields"]:
            if name not in COLUMNS or kind not in DTYPES:
                raise ValueError(
                    f"generator nexmark makes no field {name!r} of type "
                    f"{kind!r}: it makes the numeric columns {COLUMNS}")
            self.dtypes[name] = DTYPES[kind]
        if self.dtypes.get(TIME_FIELD) is not np.int64:
            raise ValueError(f"generator nexmark: {TIME_FIELD} has to be a "
                             "long field (epoch ms)")

        rng = np.random.default_rng(seed)
        # six draws an event, always, so that a seed's stream does not
        # depend on which fields are asked for
        u = [rng.random(n) for _ in range(6)]
        i = np.arange(n, dtype=np.int64)
        epoch, off = np.divmod(i + p["first_event_number"], period)
        kind = (off >= n_p).astype(np.int8) + (off >= n_p + n_a)
        bid, auc = kind == BID, kind == AUCTION
        # the newest person and auction opened at this event, from 0
        lp = epoch * n_p + np.minimum(off, n_p - 1)
        la = epoch * n_a + np.where(off < n_p, -1, np.minimum(off - n_p,
                                                              n_a - 1))
        ts = self.ts_of(i)

        def next_person(u):
            active = np.minimum(lp + 1, p["active_people"])
            return lp + 1 - active + np.floor(
                u * (active + ID_LEAD)).astype(np.int64)

        def next_auction(u):
            lo = np.maximum(la - p["in_flight_auctions"], 0)
            return lo + np.floor(u * (la - lo + 1 + ID_LEAD)).astype(np.int64)

        def expires():
            ahead = p["in_flight_auctions"] * period // n_a
            horizon = self.ts_of(i + ahead) - ts
            return ts + 1 + np.floor(
                u[3] * np.maximum(2 * horizon, 1)).astype(np.int64)

        # field -> (its values in cycle 0, what a cycle adds to each)
        span = np.broadcast_to(np.int64(self.span), n)  # no memory
        make = {
            "event_type": lambda: (kind, None),
            "id": lambda: (
                np.where(bid, 0, np.where(auc, la + p["first_auction_id"],
                                          lp + p["first_person_id"])),
                np.where(bid, 0, np.where(auc, step_a, step_p))),
            "auction": lambda: (
                bid * (np.where(_hot(u[0], p["hot_auction_ratio"]),
                                la // HOT_ROUND * HOT_ROUND,
                                next_auction(u[1])) + p["first_auction_id"]),
                bid * step_a),
            "bidder": lambda: (
                bid * (np.where(_hot(u[2], p["hot_bidder_ratio"]),
                                lp // HOT_ROUND * HOT_ROUND + 1,
                                next_person(u[3])) + p["first_person_id"]),
                bid * step_p),
            "seller": lambda: (
                auc * (np.where(_hot(u[0], p["hot_seller_ratio"]),
                                lp // HOT_ROUND * HOT_ROUND,
                                next_person(u[1])) + p["first_person_id"]),
                auc * step_p),
            "category": lambda: (
                auc * (FIRST_CATEGORY
                       + np.floor(u[2] * CATEGORIES).astype(np.int64)), None),
            "price": lambda: ((bid | auc) * _price(u[4]), None),
            "reserve": lambda: (auc * (_price(u[4]) + _price(u[5])), None),
            "expires": lambda: (auc * expires(), auc * self.span),
            TIME_FIELD: lambda: (ts, span),
        }
        self.base, self.step = {}, {}
        for name, dtype in self.dtypes.items():
            base, step = make[name]()
            self.base[name] = base.astype(dtype)
            self.step[name] = step if step is None else step.astype(
                dtype, copy=False)

    def columns(self, lo, hi, names=None):
        cycle, row = np.divmod(np.arange(lo, hi, dtype=np.int64), self.n)
        out = {}
        for name in names or self.dtypes:
            col, step = self.base[name][row], self.step[name]
            if step is not None:
                col = (col + cycle * step[row]).astype(col.dtype)
            out[name] = col
        return out

    def server(self, batch, intern):
        n_pool = self.n // batch
        plan = [
            (name, self.base[name].reshape(n_pool, batch),
             step if step is None else step.reshape(n_pool, batch))
            for name, step in self.step.items()
        ]

        def serve(j):
            cycle, k = divmod(j, n_pool)
            cols = {}
            for name, base, step in plan:
                if step is None or not cycle:
                    cols[name] = base[k]
                else:
                    cols[name] = base[k] + cycle * step[k]
            return cols, cols[TIME_FIELD]

        return serve

    def ts_of(self, i):
        return i * 1000 // self.rate + self.base_time

    def index_of(self, ts):
        return ((ts - self.base_time + 1) * self.rate - 1) // 1000


def make_pool(seed, n, cfg):
    return Pool(seed, n, cfg)
