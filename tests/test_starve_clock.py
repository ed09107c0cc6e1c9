"""The starvation clock (telemetry/starve.py): which run-loop stage
held the device idle. First the clock alone, driven by a fake ticket
and a fake monotonic clock through the spans of a bare ``StageTimes``;
then one tiny ``Job`` and one tiny ``ShardedJob`` on the CPU mesh whose
sink waits until the job's newest ticket is ready. Nothing here is a
rate or a wall-clock bound."""

import threading

import numpy as np
import pytest

from flink_siddhi_tpu import CEPEnvironment
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh
from flink_siddhi_tpu.runtime import executor
from flink_siddhi_tpu.telemetry import MetricsRegistry, StageTimes
from flink_siddhi_tpu.telemetry.legs import LEGS
from flink_siddhi_tpu.telemetry.starve import STARVED_STAGES, StarveClock

from tests.test_latency_legs import WINDOW, _job, _run
from tests.test_parallel import FIELDS, make_events


class FakeTicket:
    def __init__(self, ready=False):
        self.ready = ready
        self.polls = 0

    def is_ready(self):
        self.polls += 1
        return self.ready


class FakeTime:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, us):
        # whole microseconds on a binary-exact base: sums stay exact
        self.t += us / 1e6


def _clock():
    """A clock on a bare ledger, inside a run cycle of this thread,
    already starved (nothing was ever dispatched)."""
    stages = StageTimes()
    now = FakeTime()
    clock = stages.starve = StarveClock(stages, now=now)
    clock.cycle(True)
    assert clock.starved
    return stages, clock, now


def _starved(stages):
    return {
        k[len("starved."):]: round(v["seconds"] * 1e6)
        for k, v in stages.snapshot().items()
        if k.startswith("starved.") and v["seconds"]
    }


def _dispatch(stages, clock, now, ticket, us=5, wait=None):
    """What the executor does at a dispatch: the span, the step's call
    and, after the span has closed, the ticket window (``wait``: what
    happens while it blocks) and the ticket handed over."""
    with stages.span("dispatch"):
        now.tick(us)
        clock.issue()
        now.tick(us)
    if wait is not None:
        with stages.span("backpressure_wait"):
            wait()
    clock.watch(ticket, None)


def test_every_name_is_booked_at_zero_when_the_clock_is_made():
    stages = StageTimes()
    StarveClock(stages)
    snap = stages.snapshot()
    assert len(snap) == len(STARVED_STAGES)
    for name in STARVED_STAGES:
        assert snap["starved." + name] == {"seconds": 0.0, "count": 0}
    # a ledger without a clock books none of them
    assert not StageTimes().snapshot()


@pytest.mark.parametrize("nested", [False, True], ids=["top", "nested"])
def test_a_span_begun_starved_is_charged_to_its_top_level_stage(nested):
    stages, _clock_, now = _clock()
    with stages.span("tape_build"):
        now.tick(30)
        if nested:
            with stages.span("group_intern"):
                now.tick(50)
        now.tick(20)
    # whole, and a nested span's time under its parent's stage
    assert _starved(stages) == {"tape_build": 100 if nested else 50}
    assert "starved.group_intern" not in stages.snapshot()


def test_between_spans_and_between_cycles_have_their_own_names():
    stages, clock, now = _clock()
    now.tick(7)  # inside the cycle, before any span
    with stages.span("ingest"):
        now.tick(11)
    now.tick(13)
    clock.cycle(False)
    now.tick(17)  # the caller's time
    clock.cycle(True)
    assert _starved(stages) == {
        "between": 20, "ingest": 11, "outside_cycle": 17,
    }


def test_nothing_is_charged_between_a_dispatch_and_readiness():
    stages, clock, now = _clock()
    ticket = FakeTicket()
    _dispatch(stages, clock, now, ticket)
    before = _starved(stages)
    assert before == {"dispatch": 5}  # up to the step's call
    for stage in ("ingest", "tape_build", "drain"):
        with stages.span(stage):
            now.tick(40)
            with stages.span("inner"):
                now.tick(40)
        now.tick(3)
    assert not clock.starved and ticket.polls > 0
    assert _starved(stages) == before


def test_the_span_in_which_the_ticket_turns_ready_goes_to_onset():
    stages, clock, now = _clock()
    ticket = FakeTicket()
    _dispatch(stages, clock, now, ticket)
    with stages.span("tape_build"):
        now.tick(10)
    with stages.span("drain"):
        now.tick(25)
        ticket.ready = True  # somewhere in here the queue ran empty
        now.tick(35)
        polls = ticket.polls
        with stages.span("sink"):  # the first boundary to see it
            now.tick(8)
        now.tick(2)
    # the bracket whole to onset, what follows to the stage; and once
    # starved no further poll is made
    assert _starved(stages) == {"dispatch": 5, "onset": 60, "drain": 10}
    assert ticket.polls == polls + 1
    assert clock.starved and not clock.inflight


def test_a_poll_retires_oldest_first_and_stamps_the_records():
    class Rec:
        complete = None

    stages, clock, now = _clock()
    a, b, ra, rb = FakeTicket(), FakeTicket(), Rec(), Rec()
    for ticket, rec in ((a, ra), (b, rb)):
        with stages.span("dispatch"):
            clock.issue()
        clock.watch(ticket, rec)
    a.ready = True
    now.tick(9)
    with stages.span("ingest"):
        pass
    assert ra.complete == now() and rb.complete is None
    assert not clock.starved and len(clock.inflight) == 1
    rb.complete = 1.0  # the drain's meta was seen ready first
    b.ready = True
    with stages.span("ingest"):
        pass
    assert rb.complete == 1.0 and clock.starved


def test_a_bare_ticket_stands_for_all_before_it():
    """ShardedJob's ticket is a leaf the next step donates: the clock
    must not poll it once that step has been called."""
    stages, clock, now = _clock()
    old, new = FakeTicket(), FakeTicket()
    _dispatch(stages, clock, now, old)
    with stages.span("dispatch"):
        polls = old.polls
        clock.issue()  # polls the old one a last time
        assert old.polls == polls + 1
        now.tick(4)  # the step's call: it deletes the old leaf
        with stages.span("inner"):
            pass  # a boundary between the call and the ticket
        clock.watch(new)
    with stages.span("drain"):
        now.tick(4)
    assert old.polls == polls + 1 and new.polls > 0
    assert [t for t, _rec in clock.inflight] == [new]


def test_backpressure_wait_is_never_starved():
    stages, clock, now = _clock()
    tickets = [FakeTicket() for _ in range(4)]

    def wait():
        now.tick(500)
        for ticket in tickets:  # even if the wait emptied the queue
            ticket.ready = True

    for ticket in tickets:
        _dispatch(stages, clock, now, ticket, wait=wait)
        now.tick(1)
    snap = stages.snapshot()
    assert snap["backpressure_wait"]["count"] == 4
    assert snap["starved.backpressure_wait"] == {"seconds": 0.0, "count": 0}
    # what the wait left dry shows at the next boundary, as an onset
    assert snap["starved.onset"]["count"] >= 2


def test_a_scripted_run_sums_to_its_dry_time_to_the_microsecond():
    rng = np.random.default_rng(5)
    stages, clock, now = _clock()
    ticket, dry = None, 0  # dry: scripted microseconds with nothing queued

    def pass_time(us):
        nonlocal dry
        if ticket is None or ticket.ready:
            dry += us
        now.tick(us)

    for _cycle in range(200):
        clock.cycle(True)
        for stage in ("ingest", "reorder", "tape_build", "drain"):
            with stages.span(stage):
                for _ in range(int(rng.integers(1, 4))):
                    with stages.span("inner"):
                        if ticket is not None and rng.random() < 0.2:
                            # ready from a boundary on (the poll at this
                            # span's enter found it queued at this very
                            # time), so the onset's bracket is scripted
                            # dry time too
                            ticket.ready = True
                        pass_time(int(rng.integers(1, 400)))
        if rng.random() < 0.6:
            with stages.span("dispatch"):
                pass_time(int(rng.integers(1, 50)))
                clock.issue()
                ticket = FakeTicket()
                pass_time(int(rng.integers(1, 50)))  # the call: queued
            clock.watch(ticket)
        clock.cycle(False)
        pass_time(int(rng.integers(1, 30)))
    booked = {
        k: v for k, v in stages.snapshot().items()
        if k.startswith("starved.")
    }
    assert dry > 0 and sum(_starved(stages).values()) == dry
    assert abs(sum(v["seconds"] for v in booked.values()) * 1e6 - dry) < 1
    # every stage of the script took its share, the onset bracket too
    assert all(
        booked["starved." + k]["seconds"] > 0
        for k in ("ingest", "reorder", "tape_build", "drain", "dispatch",
                  "outside_cycle", "onset")
    )


def test_spans_of_another_thread_pass_the_clock_by():
    stages, clock, now = _clock()

    def other():
        with stages.span("drain"):
            now.tick(50)

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert "starved.drain" in stages.snapshot()  # booked at 0 only
    assert _starved(stages) == {}
    assert stages.snapshot()["drain"]["count"] == 1


def test_with_telemetry_off_no_ticket_is_polled_and_no_span_opens():
    reg = MetricsRegistry(enabled=False)
    ticket = FakeTicket()
    # even with a clock at hand that believes work is queued
    clock = reg.stages.starve = StarveClock(reg.stages, now=FakeTime())
    clock.cycle(True)
    clock.issue()
    clock.watch(ticket)
    polls = ticket.polls
    with reg.span("drain"):
        with reg.span("sink"):
            pass
    assert ticket.polls == polls
    assert "drain" not in reg.stages.snapshot()


def test_a_job_with_telemetry_off_keeps_no_clock(monkeypatch):
    monkeypatch.setattr(
        executor, "StarveClock",
        lambda *a, **k: pytest.fail("a clock was made"),
    )
    job = _job(cql=WINDOW, fused=3)
    job.telemetry.enabled = False
    _run(job)
    assert job.telemetry.stages.starve is None
    assert not job.telemetry.snapshot()["stages"]
    assert len(job.results("out")) > 0


def _wait_for_the_newest_ticket(job, calls):
    """A sink that spins until the job's newest ticket is ready: when it
    returns the device's queue is empty, whatever the backend's pace."""

    def sink(_ts, _row):
        inflight = job.telemetry.stages.starve.inflight
        while inflight and not inflight[-1][0].is_ready():
            pass
        calls.append(1)

    return sink


def _check_starved_stages(job, calls):
    stages = job.telemetry.snapshot()["stages"]
    assert calls
    for name in STARVED_STAGES:
        assert "starved." + name in stages, name
    assert stages["starved.onset"]["count"] >= 1
    # the sink returned on an empty queue: the rest of that drain
    assert stages["starved.drain"]["seconds"] > 0
    assert stages["starved.backpressure_wait"]["seconds"] == 0
    for name in ("drain.request", "drain.emit", "trace_complete",
                 "trace_stamp", "source_pull", "sink"):
        assert stages["nested." + name]["count"] > 0, name
    assert (stages["nested.drain.emit"]["seconds"]
            >= stages["nested.sink"]["seconds"] > 0)
    # charged under a top-level stage, never under a nested span's name
    assert not any(
        k.startswith("starved.nested") or k == "starved.sink"
        for k in stages
    )
    starved = sum(
        v["seconds"] for k, v in stages.items() if k.startswith("starved.")
    )
    assert 0 < starved


@pytest.mark.parametrize("fused", [0, 3], ids=["unfused", "fused"])
def test_a_tiny_job_books_the_starved_stages_and_the_legs_still_sum(fused):
    job = _job(cql=WINDOW, fused=fused)
    calls = []
    job.add_sink("out", _wait_for_the_newest_ticket(job, calls))
    _run(job)
    _check_starved_stages(job, calls)
    # the dispatch that ended a starved stretch was charged up to its call
    stages = job.telemetry.snapshot()["stages"]
    assert stages["starved.dispatch"]["count"] >= 1
    hists = {leg: job.telemetry.get_histogram("leg." + leg) for leg in LEGS}
    assert {h.count for h in hists.values()} == {40_000}
    five = sum(hists[leg].sum for leg in LEGS if leg != "total")
    assert five == hists["total"].sum > 0
    # every record's ticket was retired by the clock's polls
    clock = job.telemetry.stages.starve
    assert all(rec.complete is not None for _t, rec in clock.inflight)


def test_a_tiny_sharded_job_books_the_starved_stages():
    env = CEPEnvironment(batch_size=64)
    env.register_stream("S", make_events(640, id_mod=13), FIELDS)
    plan = compile_plan(
        "from S select id, sum(price) as total group by id insert into out",
        {"S": env.schemas["S"]}, extensions=env.extensions,
    )
    job = ShardedJob(
        [plan], [env.sources["S"]], mesh=make_cep_mesh(4), batch_size=64,
    )
    calls = []
    job.add_sink("out", _wait_for_the_newest_ticket(job, calls))
    job.run()
    _check_starved_stages(job, calls)
    stages = job.telemetry.snapshot()["stages"]
    # the mesh's ticket is for the clock alone: one entry, no record,
    # no ticket window, no legs
    clock = job.telemetry.stages.starve
    assert len(clock.inflight) <= 1
    assert all(rec is None for _t, rec in clock.inflight)
    rt = next(iter(job._plans.values()))
    assert not rt.tickets and not rt.seg_open
    assert job.telemetry.get_histogram("leg.total") is None
    assert stages["starved.dispatch"]["count"] >= 1
    assert stages["route"]["count"] == stages["dispatch"]["count"]


def test_profile_starve_lays_idle_gaps_under_the_flagged_spans():
    """scripts/profile_starve.py's reduction, on a made-up trace: the
    share of device idle time under spans the program flagged."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "profile_starve.py",
    )
    spec = importlib.util.spec_from_file_location("profile_starve", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ms = 1_000_000
    # busy 0-10, idle 10-30, busy 30-40 (two ops that touch), idle
    # 40-44, busy 44-50
    ops = [(0, 10 * ms), (30 * ms, 36 * ms), (36 * ms, 40 * ms),
           (44 * ms, 50 * ms)]
    spans = [
        ("fst.tape_build", 8 * ms, 14 * ms, False),  # the queue ran dry
        ("fst.drain", 14 * ms, 26 * ms, True),  # entered starved
        ("fst.sink", 16 * ms, 20 * ms, True),  # nested in it
        ("fst.ingest", 41 * ms, 43 * ms, False),  # believed queued
    ]
    onsets = [(13 * ms, 2 * ms)]
    red = mod.reduce(ops, spans, onsets)
    assert red["idle_ns"] == 24 * ms and red["window_ns"] == 50 * ms
    # 10-14 under the onset span, 14-26 under the starved one
    assert red["flagged_ns"] == 16 * ms
    assert red["in_spans_ns"] == 18 * ms
    first, second = red["gaps"]
    assert (first["ns"], first["flagged"]) == (20 * ms, 16 * ms)
    assert [(n, m) for n, m, _ns in first["cover"]] == [
        ("fst.drain", "S"), ("fst.tape_build", "O"), ("fst.sink", "S")]
    assert (second["ns"], second["flagged"]) == (4 * ms, 0)
    assert second["cover"] == [("fst.ingest", "-", 2 * ms)]
