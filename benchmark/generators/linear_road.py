"""Linear Road's position reports at the benchmark's peak, held: the
stream of a variable tolling system over ``expressways`` expressways
(Arasu et al., "Linear Road: A Stream Data Management Benchmark", VLDB
2004), at the source's record width.

**Every rule and every value here is from memory of the paper and of its
data generator (MITSIM's output format)** (no network in this sandbox):
a configuration lists each parameter under its ``assumed`` as such, and a
reader with the source at hand checks them against it.

Fields, the source's fifteen: ``type`` (0 a position report; 2, 3, 4
the account-balance, daily-expenditure and travel-time requests),
``time`` (long, ms), ``vid``, ``spd`` (mph), ``xway``, ``lane``,
``dir``, ``seg``, ``pos`` (feet from the expressway's western end,
``seg`` = ``pos // 5280``), and the requests' own ``qid`` (every type
of request), ``sinit``, ``send``, ``dow``, ``tod`` (type 4: from and to
segment, day of the week 1-7, minute of the day 1-1440) and ``day``
(type 3: 1-69). A row holds -1 where its type has no value (the
source's null): a position report in the last six, a request in the
position fields (types 3 and 4 name their expressway).

**The round.** Every vehicle reports its position every
``report_period_s`` (30) seconds, each at its own phase. A tick of
``tick_ms`` (1,000) holds ``expressways * reports_per_s_per_xway``
events, all stamped with the tick's start; a round is a report period of
ticks, and event ``i`` is slot ``i % round`` of round ``i // round``. A
slot is a vehicle's place in the round: the same vehicle reports there
round after round until its trip ends, and then the slot goes to a new
vehicle with a new, higher ``vid`` (``trip number * round + slot``).
``other_request_permille`` of the slots carry a request of type 2-4
instead, with the ``vid`` of some live vehicle.

**A trip** lasts ``trip_reports_min`` .. ``trip_reports_max`` reports
(a slot's own draw; at event 0 the slots are at every phase of their
trips: the stream starts mid-flow). The vehicle keeps its expressway,
direction, travel lane (1-3; the entry lane 0 and the exit lane 4 are
not modelled) and speed (10-75 mph, congested to free-flowing), enters at a position that changes
from trip to trip and moves ``spd * 44`` feet a report: a moving vehicle
never repeats a position.

**Accidents.** Each pool period starts ``expressways * period /
accident_every_s`` (rounded, at least 1) accidents, evenly through the
round's ticks from a start the seed draws, at places it draws: two
vehicles drive up for ``APPROACH`` reports and then
hold one (xway, dir, lane, pos) for ``accident_reports`` reports. The
slots they use are set aside for accidents, a pair for each of the
accident's instances that overlap in time (the next period starts the
same accident again, in the same tick of the round, with other vehicles
at a place moved on); what is left of such a slot's time goes to one
more short trip. So the number of reports per tick that are a vehicle's
fourth or later at one place repeats with the pool, from the pool's
second cycle on (in the first, windows that began before event 0 are
short of their first reports).

**Cycles.** The draws are per slot and per accident, so they repeat with
the round and the pool (``n`` is a whole number of rounds); trip numbers,
``vid`` and ``time`` run on with the event number, in closed form.
"""

from __future__ import annotations

import numpy as np

FIELDS = ("type", "time", "vid", "spd", "xway", "lane", "dir", "seg", "pos",
          "qid", "sinit", "send", "dow", "tod", "day")
TIME_FIELD = "time"
DTYPES = {"int": np.int32, "long": np.int64}
PARAMS = (
    "expressways", "reports_per_s_per_xway", "report_period_s", "tick_ms",
    "other_request_permille", "trip_reports_min", "trip_reports_max",
    "accident_every_s", "accident_reports", "base_time_ms",
)
NULL = -1  # a request row's position fields
SEG_FEET, MAX_POS = 5_280, 527_999  # 100 segments of a mile
FEET_PER_MPH = 44  # feet in 30 s at 1 mph
SPD_LO, SPD_HI = 10, 75  # congested to free-flowing
APPROACH = 4  # reports a vehicle drives before it stops at an accident
# a trip enters in [0, ENTRY_SPAN) and moves at most 60 x 75 x 44 feet
ENTRY_SPAN, ENTRY_STEP = 320_000, 104_729
# an accident lies in [ACC_LO, ACC_LO + ACC_SPAN), moved on each cycle
ACC_LO, ACC_SPAN, ACC_STEP = 200_000, 128_000, 36_007


class Pool:
    def __init__(self, seed: int, n: int, cfg) -> None:
        try:
            p = {k: int(cfg[k]) for k in PARAMS}
        except KeyError as e:
            raise ValueError(
                f"generator linear_road: the configuration lacks {e}; it "
                f"states every parameter itself ({', '.join(PARAMS)})"
            ) from None
        self.dtypes = {}
        for name, kind in cfg["fields"]:
            if name not in FIELDS or kind not in DTYPES:
                raise ValueError(
                    f"generator linear_road makes no field {name!r} of "
                    f"type {kind!r}: it makes {FIELDS}")
            self.dtypes[name] = DTYPES[kind]
        if self.dtypes.get(TIME_FIELD) is not np.int64:
            raise ValueError(f"generator linear_road: {TIME_FIELD} has to "
                             "be a long field (ms)")
        self.tick_ms, self.base_time = p["tick_ms"], p["base_time_ms"]
        period_ms = p["report_period_s"] * 1000
        if period_ms % self.tick_ms:
            raise ValueError("generator linear_road: the report period is "
                             "not a whole number of ticks")
        ticks = period_ms // self.tick_ms
        self.per_tick = pt = (
            p["expressways"] * p["reports_per_s_per_xway"]
            * self.tick_ms // 1000)
        self.round = R = pt * ticks
        if n % R:
            raise ValueError(
                f"generator linear_road: a pool of {n} events cannot "
                f"cycle: it has to be a whole number of rounds ({R})")
        self.n = n
        P = n // R  # rounds a pool period
        self.trip_acc = p["accident_reports"] + APPROACH
        # an accident's instances that overlap in time, a slot pair each
        self.overlap = D = -(-self.trip_acc // P)
        self.res_period = D * P  # rounds: the accident's trip, then one more
        n_acc = max(1, round(
            p["expressways"] * P * p["report_period_s"]
            / p["accident_every_s"]))
        if n_acc * 2 * D > pt:
            raise ValueError(
                f"generator linear_road: {n_acc} accidents a period need "
                f"{n_acc * 2 * D} slots of a tick's {pt}")

        rng = np.random.default_rng(seed)
        request = rng.random(R) * 1000 < p["other_request_permille"]
        kind = 2 + rng.integers(0, 3, R)
        self.xway = rng.integers(0, p["expressways"], R).astype(np.int32)
        self.dir = rng.integers(0, 2, R).astype(np.int32)
        self.lane = rng.integers(1, 4, R).astype(np.int32)
        self.spd = rng.integers(SPD_LO, SPD_HI + 1, R).astype(np.int32)
        self.trip = rng.integers(
            p["trip_reports_min"], p["trip_reports_max"] + 1, R)
        self.phase = np.floor(rng.random(R) * self.trip).astype(np.int64)
        self.entry = rng.integers(0, ENTRY_SPAN, R)

        # the accidents' slots: a tick each, 2 * D places in it. The
        # ticks lie evenly through the round from a drawn start (an
        # accident "every so often"), so every stretch of the stream of
        # a few ticks holds its share of stopped vehicles whatever the
        # seed
        acc_tick = np.floor(
            (np.arange(n_acc) + rng.random()) * ticks / n_acc
        ).astype(np.int64) % ticks
        place = rng.permutation(pt)[: n_acc * 2 * D].reshape(n_acc, 2 * D)
        slots = (acc_tick[:, None] * pt + place).reshape(-1)
        acc = np.repeat(np.arange(n_acc), 2 * D)
        self.res_slot = slots
        # the instance of cycle c uses pair c % D; it starts in round
        # first_round of its pool period
        first_round = rng.integers(0, P, n_acc)
        pair = np.tile(np.repeat(np.arange(D), 2), n_acc)
        self.res_start = pair * P + first_round[acc]
        self.res_pos = rng.integers(0, ACC_SPAN, n_acc)[acc]
        self.res_of = np.full(R, -1, dtype=np.int64)
        self.res_of[slots] = np.arange(len(slots))
        request[slots] = False
        for col, draw in ((self.xway, rng.integers(0, p["expressways"], n_acc)),
                          (self.dir, rng.integers(0, 2, n_acc)),
                          (self.lane, rng.integers(1, 4, n_acc))):
            col[slots] = draw[acc]

        self.type = np.where(request, kind, 0).astype(np.int32)
        self.request = request
        # a request carries the vid of a vehicle on an ordinary trip
        vehicles = np.flatnonzero(~request & (self.res_of < 0))
        self.asks_for = np.zeros(R, dtype=np.int64)
        self.asks_for[request] = vehicles[
            rng.integers(0, len(vehicles), int(request.sum()))]
        for col in (self.dir, self.lane, self.spd):
            col[request] = NULL
        self.xway[request & (kind == 2)] = NULL
        # the requests' own fields (drawn last: the draws above are what
        # they were before there were any): a slot's request is the same
        # question round after round under a new ``qid``
        self.req_rank = np.cumsum(request) - 1
        self.n_req = int(request.sum())
        segs = MAX_POS // SEG_FEET + 1
        self.req_cols = {}
        for name, lo, hi, kinds in (
            ("sinit", 0, segs, (4,)), ("send", 0, segs, (4,)),
            ("dow", 1, 8, (4,)), ("tod", 1, 1441, (4,)),
            ("day", 1, 70, (3,)),
        ):
            col = rng.integers(lo, hi, R).astype(np.int32)
            col[~(request & np.isin(kind, kinds))] = NULL
            self.req_cols[name] = col

    # -- the columns ---------------------------------------------------------
    def _make(self, r, s, names, at=None):
        """Columns ``names`` of the events at slots ``s`` (a slice or an
        index array) of round(s) ``r`` (one, or one per event). ``at``:
        the positions among them of the requests and of the accidents'
        slots, where the caller knows them."""
        R = self.round
        slots = (np.arange(s.start, s.stop, dtype=np.int64)
                 if isinstance(s, slice) else s)
        if at is None:
            at = (np.flatnonzero(self.request[s]),
                  np.flatnonzero(self.res_of[s] >= 0))
        req, res = at
        r_of = (lambda i: r) if np.ndim(r) == 0 else (lambda i: r[i])
        out = {}
        if "type" in names:
            out["type"] = self.type[s]
        if "time" in names:
            out["time"] = self.ts_of(r * R + slots)
        for name in ("xway", "dir", "lane"):
            if name in names:
                out[name] = getattr(self, name)[s]
        for name, col in self.req_cols.items():
            if name in names:
                out[name] = col[s]
        if "qid" in names:
            qid = np.full(len(slots), NULL, dtype=np.int64)
            qid[req] = (
                r_of(req) * self.n_req + self.req_rank[slots[req]]
            ) % (1 << 31)
            out["qid"] = qid
        k, m = np.divmod(r + self.phase[s], self.trip[s])
        # the accidents' slots: the accident's trip, then one more
        j = self.res_of[slots[res]]
        cyc, q = np.divmod(r_of(res) - self.res_start[j], self.res_period)
        stopped_trip = q < self.trip_acc
        if "vid" in names:
            vid = k * R + slots
            b = self.asks_for[slots[req]]
            vid[req] = (r_of(req) + self.phase[b]) // self.trip[b] * R + b
            vid[res] = (2 * cyc + 2 + ~stopped_trip) * R + slots[res]
            out["vid"] = vid
        m_res = np.where(stopped_trip, q, q - self.trip_acc)
        if "spd" in names:
            spd = self.spd[s].copy()
            spd[res] = np.where(
                stopped_trip & (m_res >= APPROACH), 0, spd[res])
            out["spd"] = spd
        if "pos" in names or "seg" in names:
            step = self.spd[s].astype(np.int64) * FEET_PER_MPH
            along = (self.entry[s] + k * ENTRY_STEP) % ENTRY_SPAN + m * step
            # the accident's place moves on each time it is started
            # again; a vehicle drives up to it, and the slot's next
            # vehicle drives on from it
            place = ACC_LO + (self.res_pos[j] + cyc * ACC_STEP) % ACC_SPAN
            along[res] = place + step[res] * np.where(
                stopped_trip, np.minimum(m_res - APPROACH, 0), m_res + 1)
            pos = np.where(self.dir[s] == 0, along, MAX_POS - along)
            pos[req] = NULL
            if "pos" in names:
                out["pos"] = pos
            if "seg" in names:
                out["seg"] = pos // SEG_FEET
        return {k: out[k].astype(self.dtypes[k], copy=False)
                for k in self.dtypes if k in out}

    def columns(self, lo, hi, names=None):
        """Round by round: within one, the slots are a slice."""
        R, names = self.round, set(names or self.dtypes)
        parts = [
            self._make(r, slice(max(lo - r * R, 0), min(hi - r * R, R)),
                       names)
            for r in range(lo // R, -(-hi // R))
        ] if hi > lo else [self._make(0, slice(0, 0), names)]
        if len(parts) == 1:
            return parts[0]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    def server(self, batch, intern):
        R = self.round
        if R % batch:
            raise ValueError(
                f"generator linear_road: a round of {R} events is not a "
                f"whole number of batches of {batch}")
        names = set(self.dtypes)
        cuts = []
        for b in range(R // batch):
            s = slice(b * batch, (b + 1) * batch)
            cuts.append((s, (np.flatnonzero(self.request[s]),
                             np.flatnonzero(self.res_of[s] >= 0))))

        def serve(j):
            r, b = divmod(j, len(cuts))
            s, at = cuts[b]
            cols = self._make(r, s, names, at)
            return cols, cols[TIME_FIELD]

        return serve

    # -- the event clock -----------------------------------------------------
    def ts_of(self, i):
        return i // self.per_tick * self.tick_ms + self.base_time

    def index_of(self, ts):
        return ((ts - self.base_time) // self.tick_ms + 1) * self.per_tick - 1


def make_pool(seed, n, cfg):
    return Pool(seed, n, cfg)
