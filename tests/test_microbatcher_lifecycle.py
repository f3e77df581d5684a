"""A batch's life on the loop's clock (``workflow/create_server._MicroBatcher``):
what the loop was doing (``pio_batch_loop_seconds_total{state}`` beside
``pio_batch_slot_wait_seconds_total``), what closed a batch
(``pio_batch_closed_total{after}``), where the other callers were then
(``pio_batch_inflight_at_close_total``) and how evenly the device answers
(``pio_batch_answer_gap_seconds_total`` and its squares).

On ``tests/test_batcher_slots.py``'s rig: a real ``QueryServer`` whose
dispatch is a stub device, each batch's ``finalize`` blocking on events the
test sets.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from predictionio_tpu.obs.waterfall import PHASE_QUEUE_WAIT
from predictionio_tpu.resilience import Deadline, DeadlineExceeded
from predictionio_tpu.workflow.create_server import ShuttingDownError
from tests.test_batcher_slots import (
    StubDevice,
    a_stream_of_batches_never_puts_three_ahead,
    ask,
    queue_behind,
    take_both_slots,
)
from tests.test_obs_spans import make_server, metrics_of, until

LOOP = 'pio_batch_loop_seconds_total{state="%s"}'
SLOT_WAIT = "pio_batch_slot_wait_seconds_total"
CLOSED = 'pio_batch_closed_total{after="%s"}'
QUERIES = 'pio_batch_closed_queries_total{after="%s"}'
CUT = "pio_batch_cut_total"
INFLIGHT = "pio_batch_inflight_at_close_total"
GAP = "pio_batch_answer_gap_seconds_total"
GAP_SQUARED = "pio_batch_answer_gap_squared_seconds_total"
AFTER = ("slot", "idle", "dispatch")
NEW_SERIES = (
    [LOOP % state for state in ("idle", "collect", "dispatch")]
    + [family % after for family in (CLOSED, QUERIES) for after in AFTER]
    + [CUT, INFLIGHT, GAP, GAP_SQUARED]
)


class SlowDevice(StubDevice):
    """A stub device whose dispatch itself takes ``dispatch_s`` on the
    dispatch thread: a launch that waits in the device's queue."""

    dispatch_s = 0.0

    def __call__(self, items, batch_no=0):
        time.sleep(self.dispatch_s)
        return super().__call__(items, batch_no)


def drive(scenario, device=None, **config):
    """``tests.test_batcher_slots.drive`` with every reading of the loop's
    clock kept: ``server.ticks`` is ``[(state, since, now)]``."""
    server = make_server(**config)
    device = device or SlowDevice()
    server._dispatch_query_batch = device
    batcher = server._batcher
    server.ticks, tick = [], batcher._tick

    def keeping(state, since):
        now = tick(state, since)
        server.ticks.append((state, since, now))
        return now

    batcher._tick = keeping

    async def body():
        try:
            await scenario(server, device)
        finally:
            for batch in device.batches:
                batch.release()
            batcher.close()
            await batcher.wait_closed()

    asyncio.run(body())
    return server


def closed(scraped) -> dict:
    return {after: (scraped[CLOSED % after], scraped[QUERIES % after]) for after in AFTER}


def clocks(scraped) -> dict:
    out = {state: scraped[LOOP % state] for state in ("idle", "collect", "dispatch")}
    return dict(out, slot_wait=scraped[SLOT_WAIT])


async def answered(server, device, asked):
    for batch in device.batches:
        batch.release()
    await asyncio.gather(*asked)
    await until(lambda: not server._batcher._finish_tasks)


# -- the loop's clock ---------------------------------------------------------


async def idle_grows(server, device):
    device.auto = True
    await asyncio.gather(ask(server, 1), ask(server, 2))
    await asyncio.sleep(0.3)  # the queue is empty
    await asyncio.gather(ask(server, 3), ask(server, 4))


async def slot_wait_grows(server, device):
    first, second = await take_both_slots(server, device)
    waiting = await queue_behind(server, [4, 5])
    await asyncio.sleep(0.3)  # both slots taken, two queries pending
    await answered(server, device, first + second + waiting)


async def collect_grows(server, device):
    device.auto = True
    observe = server.waterfall.observe

    def slow(phase, seconds, trace_id=None):
        if phase == PHASE_QUEUE_WAIT:
            time.sleep(0.1)  # on the loop, with the slot in hand
        observe(phase, seconds, trace_id)

    server.waterfall.observe = slow
    await asyncio.gather(ask(server, 1), ask(server, 2), ask(server, 3))


async def dispatch_grows(server, device):
    device.auto, device.dispatch_s = True, 0.3
    await asyncio.gather(ask(server, 1), ask(server, 2))


@pytest.mark.parametrize(
    "scenario, grows",
    [
        (idle_grows, "idle"),
        (slot_wait_grows, "slot_wait"),
        (collect_grows, "collect"),
        (dispatch_grows, "dispatch"),
    ],
    ids=["idle", "slot_wait", "collect", "dispatch"],
)
def test_the_four_clocks_tile_the_loops_wall_and_the_scenarios_own_grows(scenario, grows):
    server = drive(scenario)
    seconds = clocks(metrics_of(server))
    wall = server.ticks[-1][2] - server.ticks[0][1]
    assert sum(seconds.values()) == pytest.approx(wall, rel=0.02)
    assert seconds[grows] > 0.25
    assert all(s < 0.1 for state, s in seconds.items() if state != grows), seconds
    # consecutive readings: an interval starts where the one before it ended,
    # but for `collect`, which starts where the slot wait's own counter ends
    for (_, _, before), (state, since, _) in zip(server.ticks, server.ticks[1:]):
        assert since >= before if state == "collect" else since == before


# -- what closed a batch ------------------------------------------------------


async def a_lone_arrival_on_an_idle_loop(server, device):
    device.auto = True
    assert await ask(server, 1) == "answer:1"


async def arrivals_during_a_slot_wait(server, device):
    first, second = await take_both_slots(server, device)
    waiting = await queue_behind(server, [4])
    waiting += await queue_behind(server, [5, 6])
    device.batches[0].answer.set()
    await until(lambda: len(device.batches) == 3)
    await answered(server, device, first + second + waiting)


async def a_cohort_queued_while_a_slow_dispatch_holds_the_loop(server, device):
    device.auto, device.dispatch_s = True, 0.5
    first = [ask(server, 1), ask(server, 2)]
    await until(lambda: server._batcher._batch_seq == 1)  # closed, being dispatched
    cohort = await queue_behind(server, [3, 4, 5])
    assert not device.batches  # the dispatch thread is still in the first launch
    await answered(server, device, first + cohort)
    assert [batch.users for batch in device.batches] == [[1, 2], [3, 4, 5]]


@pytest.mark.parametrize(
    "scenario, expected",
    [
        (a_lone_arrival_on_an_idle_loop, {"idle": (1, 1)}),
        # [1, 2] and [3] each woke an idle loop with a slot free
        (arrivals_during_a_slot_wait, {"idle": (2, 3), "slot": (1, 3)}),
        (a_cohort_queued_while_a_slow_dispatch_holds_the_loop, {"idle": (1, 2), "dispatch": (1, 3)}),
    ],
    ids=["idle", "slot", "dispatch"],
)
def test_a_batch_is_closed_after_what_the_loop_last_waited_on(scenario, expected):
    server = drive(scenario)
    scraped = metrics_of(server)
    assert closed(scraped) == {after: expected.get(after, (0, 0)) for after in AFTER}
    batcher = server._batcher
    assert sum(n for n, _ in closed(scraped).values()) == batcher.batches_dispatched
    assert sum(q for _, q in closed(scraped).values()) == batcher.queries_dispatched
    assert scraped[CUT] == 0


# -- cut at the limit ---------------------------------------------------------


class Limited:
    """An algorithm whose batches hold three queries at most."""

    def register_metrics(self, registry):
        pass

    def batch_limit(self):
        return 3

    def warmup_serving(self, model, max_batch_size):
        pass


async def six_queue_behind_two_taken_slots(server, device):
    first, second = await take_both_slots(server, device)
    waiting = await queue_behind(server, [4, 5, 6, 7, 8, 9])
    device.batches[0].answer.set()
    await until(lambda: len(device.batches) == 3)
    assert len(device.batches[2].users) == server._batcher.max_batch
    await answered(server, device, first + second + waiting)


@pytest.mark.parametrize("limit", ["max_batch_size", "batch_limit"])
def test_a_drain_that_stops_at_the_limit_with_queries_left_is_cut(limit):
    async def scenario(server, device):
        if limit == "batch_limit":
            server._warmup_components([Limited()], [None])
        await six_queue_behind_two_taken_slots(server, device)

    server = drive(scenario, max_batch_size=4 if limit == "max_batch_size" else 64)
    scraped = metrics_of(server)
    # [1, 2], [3], then 4 of the six and 2 (or 3 and 3): only the first drain
    # left queries behind it
    assert server._batcher.max_batch == (4 if limit == "max_batch_size" else 3)
    assert scraped[CUT] == 1
    assert server._batcher.batches_dispatched == 4


# -- callers in flight at a close ---------------------------------------------


async def ends_with_a_result(server, device):
    first, second = await take_both_slots(server, device)
    await until(lambda: server._batcher.batches_dispatched == 2)
    # [3] was closed while [1, 2] was unresolved
    assert metrics_of(server)[INFLIGHT] == 2
    waiting = await queue_behind(server, [4])
    device.batches[0].answer.set()  # answered by the device, still being served
    await until(lambda: server._batcher.batches_dispatched == 3)
    assert metrics_of(server)[INFLIGHT] == 2 + 3 and server._batcher._inflight == 4
    await answered(server, device, first + second + waiting)


async def ends_with_an_exception(server, device):
    first, second = await take_both_slots(server, device)
    device.batches[0].fail = RuntimeError("the device fell over")
    device.batches[0].answer.set()
    with pytest.raises(RuntimeError, match="fell over"):
        await first[0]
    await until(lambda: server._batcher._inflight == 1)
    await answered(server, device, second)


async def ends_with_a_watchdog_trip(server, device):
    hung = [ask(server, 1, Deadline(0.2)), ask(server, 2)]
    await until(lambda: server._batcher._inflight == 2)
    with pytest.raises(DeadlineExceeded, match="micro-batch fetch"):
        await hung[0]
    await until(lambda: not server._batcher._finish_tasks)


async def ends_with_close(server, device):
    first, second = await take_both_slots(server, device)
    await until(lambda: server._batcher._inflight == 3)
    server._batcher.close()
    for asked in first + second:
        with pytest.raises(ShuttingDownError):
            await asked
    await server._batcher.wait_closed()


@pytest.mark.parametrize(
    "scenario",
    [ends_with_a_result, ends_with_an_exception, ends_with_a_watchdog_trip, ends_with_close],
    ids=["result", "exception", "watchdog", "close"],
)
def test_the_tally_of_callers_in_flight_is_back_at_zero(scenario):
    server = drive(scenario)
    assert server._batcher._inflight == 0


def test_close_in_the_loop_turn_of_a_dispatchs_return_answers_every_caller():
    """``close()`` cancels a ``_finish`` task before its first step: the
    task's body, which answers a cancelled batch, never runs, so ``close()``
    itself fails the batches that have not started."""

    async def scenario(server, device):
        batcher, tick = server._batcher, server._batcher._tick

        def closing(state, since):
            now = tick(state, since)
            if state == "dispatch":  # `_finish` was scheduled in this very step
                batcher.close()
            return now

        batcher._tick = closing
        asked = [ask(server, 1), ask(server, 2)]
        t0 = time.perf_counter()
        for caller in asked:
            with pytest.raises(ShuttingDownError):
                await asyncio.wait_for(caller, 2.0)
        assert time.perf_counter() - t0 < 1.0
        assert device.batches and not device.batches[0].thread  # its finalize never ran
        await batcher.wait_closed()

    server = drive(scenario)
    assert server._batcher._inflight == 0 and server._batcher.batches_dispatched == 1


# -- how evenly the device answers --------------------------------------------

T = 0.1
ROUNDS = 4


async def pair_dispatched(server, device, users):
    n = len(device.batches)
    asked = [ask(server, user) for user in users]
    await until(lambda: len(device.batches) == n + 1)
    return asked


async def two_batches_answered_together(server, device):
    asked = []
    for r in range(ROUNDS):
        start = asyncio.get_running_loop().time()
        asked += await pair_dispatched(server, device, [4 * r, 4 * r + 1])
        asked += await pair_dispatched(server, device, [4 * r + 2, 4 * r + 3])
        await asyncio.sleep(start + 2 * T - asyncio.get_running_loop().time())
        for batch in device.batches[-2:]:
            batch.release()
        await asyncio.gather(*asked)
        await until(lambda: not server._batcher._finish_tasks)


async def the_same_two_staggered(server, device):
    """Two batches ahead of the device at every moment, one answered every T."""
    asked = await pair_dispatched(server, device, [0, 1])
    start = asyncio.get_running_loop().time()
    for r in range(1, 2 * ROUNDS + 1):
        asked += await pair_dispatched(server, device, [2 * r, 2 * r + 1])
        await asyncio.sleep(start + r * T - asyncio.get_running_loop().time())
        device.batches[r - 1].release()
        await until(lambda: device.batches[r - 1].answered)
    await answered(server, device, asked)


@pytest.mark.parametrize(
    "scenario, gap_s",
    [(two_batches_answered_together, 2 * T), (the_same_two_staggered, T)],
    ids=["together", "staggered"],
)
def test_the_weighted_gap_is_the_gap_a_random_busy_moment_falls_into(scenario, gap_s):
    scraped = metrics_of(drive(scenario))
    assert scraped[GAP_SQUARED] / scraped[GAP] == pytest.approx(gap_s, rel=0.1)
    # either way the device was owed answers for the whole busy time
    assert scraped[GAP] == pytest.approx(2 * ROUNDS * T, rel=0.15)


def test_an_idle_hour_between_two_batches_adds_nothing():
    async def scenario(server, device):
        device.auto = True
        await asyncio.gather(ask(server, 1), ask(server, 2))
        await until(lambda: not server._batcher._finish_tasks)
        before = metrics_of(server)[GAP]
        server._batcher._last_answer -= 3600.0  # the last answer came an hour ago
        await asyncio.gather(ask(server, 3), ask(server, 4))
        await until(lambda: not server._batcher._finish_tasks)
        assert metrics_of(server)[GAP] - before < 1.0

    scraped = metrics_of(drive(scenario))
    assert scraped[GAP_SQUARED] < 1.0


# -- the families -------------------------------------------------------------


@pytest.mark.parametrize("series", NEW_SERIES)
def test_every_new_series_is_scraped_at_zero_before_the_first_query(series):
    assert metrics_of(make_server())[series] == 0.0


def test_a_stream_of_batches_leaves_the_books_balanced():
    """300 queries in bursts through four fetch threads at a short switch
    interval: every dispatched batch was closed after something, every
    query is in one of them, nobody is left in flight and the four clocks
    still tile."""
    server = drive(a_stream_of_batches_never_puts_three_ahead, StubDevice(), queue_high_water=0)
    scraped, batcher = metrics_of(server), server._batcher
    assert sum(n for n, _ in closed(scraped).values()) == batcher.batches_dispatched > 20
    assert sum(q for _, q in closed(scraped).values()) == batcher.queries_dispatched == 300
    assert batcher._inflight == 0
    wall = server.ticks[-1][2] - server.ticks[0][1]
    assert sum(clocks(scraped).values()) == pytest.approx(wall, rel=0.02)
    assert scraped[GAP] <= wall and scraped[GAP_SQUARED] <= scraped[GAP] ** 2
