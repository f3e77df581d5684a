"""The micro-batcher's slots (``workflow/create_server._MicroBatcher``): a
slot is a batch the device has not answered yet, there are two, and a batch
is closed at the moment it gets one.

The server is a real ``QueryServer`` whose dispatch is replaced by a stub
device: each batch's ``finalize`` blocks on two events the test sets, the
device's answer and the end of the host's serve.
"""

from __future__ import annotations

import asyncio
import random
import sys
import threading
import time

import pytest

from predictionio_tpu.resilience import Deadline, DeadlineExceeded
from predictionio_tpu.workflow.create_server import (
    LoadShedError,
    ShuttingDownError,
    _MicroBatcher,
)
from tests.test_obs_spans import make_server, metrics_of, until


class StubBatch:
    def __init__(self, users, auto, device_s=0.0, serve_s=0.0):
        self.users = users
        self.device_s, self.serve_s = device_s, serve_s  # slept before each event's wait
        self.answer = threading.Event()  # the device's answer
        self.serve = threading.Event()  # the host is done serving it
        self.fail: BaseException | None = None  # raised in place of the answer
        self.answered = False
        self.signalled = False  # finalize was handed the slot's callback
        self.thread = ""
        if auto:
            self.release()

    def release(self):
        self.answer.set()
        self.serve.set()

    def finalize(self, device_answered=None):
        self.thread = threading.current_thread().name
        time.sleep(self.device_s)
        self.answer.wait(10)
        self.answered = True
        if self.fail is not None:
            raise self.fail
        if device_answered is not None:
            self.signalled = True
            device_answered()
        time.sleep(self.serve_s)
        self.serve.wait(10)
        return [(f"answer:{user}", "v1") for user in self.users]


class StubDevice:
    """Stands in for ``QueryServer._dispatch_query_batch``."""

    def __init__(self):
        self.batches: list[StubBatch] = []
        self.auto = False  # new batches answer and serve at once
        self.most_ahead = 0  # most batches dispatched and not yet answered
        self.jitter: random.Random | None = None  # draws each batch's device and serve times

    def __call__(self, items, batch_no=0):
        times = [self.jitter.uniform(0, 0.004) for _ in range(2)] if self.jitter else []
        batch = StubBatch([item.payload["user"] for item in items], self.auto, *times)
        self.batches.append(batch)
        self.most_ahead = max(self.most_ahead, sum(not b.answered for b in self.batches))
        return batch.finalize


def ask(server, user, deadline=None):
    return asyncio.ensure_future(server._batcher.submit({"user": user}, deadline))


async def take_both_slots(server, device):
    """Two batches dispatched and unanswered: [1, 2] (two at once, so not the
    solo fast path) and [3]."""
    first = [ask(server, 1), ask(server, 2)]
    await until(lambda: len(device.batches) == 1)
    second = [ask(server, 3)]
    await until(lambda: len(device.batches) == 2)
    return first, second


async def queue_behind(server, users, **kw):
    depth = server._batcher.queue_depth
    asked = [ask(server, user, **kw) for user in users]
    await until(lambda: server._batcher.queue_depth == depth + len(users))
    return asked


def free_slots(server) -> int:
    return server._batcher._slots._value


async def settles_with_both_slots_back(server, device):
    """A following query is served, every blocked finalize runs to its end
    (a late ``device_answered`` among them), and exactly two slots are free."""
    device.auto = True
    assert await ask(server, 99) == "answer:99"
    for batch in device.batches:
        batch.release()
    await until(lambda: all(b.answered for b in device.batches))
    await asyncio.sleep(0.05)
    assert free_slots(server) == _MicroBatcher.SLOTS == 2


async def arrivals_in_the_slot_wait_ride_the_batch_in_order(server, device):
    await take_both_slots(server, device)
    await queue_behind(server, [4])
    await queue_behind(server, [5, 6])  # while the batcher waits for a slot
    assert len(device.batches) == 2 and server._batcher.queue_depth == 3
    device.batches[0].answer.set()
    await until(lambda: len(device.batches) == 3)
    assert device.batches[2].users == [4, 5, 6]
    assert server._batcher.queue_depth == 0


async def at_max_batch_the_batch_closes_and_the_rest_stay_queued(server, device):
    await take_both_slots(server, device)
    await queue_behind(server, [4, 5, 6, 7, 8, 9])
    device.batches[0].answer.set()
    await until(lambda: len(device.batches) == 3)
    assert device.batches[2].users == [4, 5, 6, 7]
    assert server._batcher.queue_depth == 2
    device.batches[1].answer.set()
    await until(lambda: len(device.batches) == 4)
    assert device.batches[3].users == [8, 9]


async def two_ahead_and_the_third_goes_when_the_first_is_answered(server, device):
    first, second = await take_both_slots(server, device)
    await queue_behind(server, [4])
    await asyncio.sleep(0.05)
    assert len(device.batches) == 2  # no third batch ahead of the device
    device.batches[0].answer.set()  # answered, and still being served
    await until(lambda: len(device.batches) == 3)
    assert not any(f.done() for f in first)
    await queue_behind(server, [5])
    await asyncio.sleep(0.05)
    assert len(device.batches) == 3
    device.batches[1].answer.set()
    await until(lambda: len(device.batches) == 4)
    assert device.most_ahead == 2
    device.batches[0].serve.set()
    assert await asyncio.gather(*first) == ["answer:1", "answer:2"]


async def the_slot_comes_back_once_after_a_finalize_that_raises(server, device):
    first, _ = await take_both_slots(server, device)
    device.batches[0].fail = RuntimeError("the device fell over")
    device.batches[0].answer.set()
    with pytest.raises(RuntimeError, match="fell over"):
        await first[0]
    await settles_with_both_slots_back(server, device)


async def the_slot_comes_back_once_after_a_watchdog_trip(server, device):
    hung = [ask(server, 1, Deadline(0.3)), ask(server, 2)]
    await until(lambda: len(device.batches) == 1)
    with pytest.raises(DeadlineExceeded, match="micro-batch fetch"):
        await hung[0]
    assert server._batcher.watchdog_trips == 1
    await settles_with_both_slots_back(server, device)


async def the_slot_comes_back_once_after_a_cancellation(server, device):
    first = [ask(server, 1), ask(server, 2)]
    # its finalize runs on a fetch thread: _finish is past its first step (one
    # cancelled before that: tests/test_microbatcher_lifecycle.py)
    await until(lambda: device.batches and device.batches[0].thread)
    (finishing,) = server._batcher._finish_tasks
    finishing.cancel()
    with pytest.raises(ShuttingDownError):
        await first[0]
    await settles_with_both_slots_back(server, device)


async def an_expired_or_abandoned_query_is_not_dispatched(server, device):
    await take_both_slots(server, device)
    (gone,) = await queue_behind(server, [4])
    (expiring,) = await queue_behind(server, [5], deadline=Deadline(0.03))
    await queue_behind(server, [6])
    gone.cancel()  # its client left
    await asyncio.sleep(0.06)
    device.batches[0].answer.set()
    await until(lambda: len(device.batches) == 3)
    assert device.batches[2].users == [6]
    with pytest.raises(DeadlineExceeded, match="admission queue"):
        await expiring


async def close_while_waiting_fails_the_queued_queries(server, device):
    first, second = await take_both_slots(server, device)
    waiting = await queue_behind(server, [4, 5])
    server._batcher.close()
    for asked in waiting + first + second:
        with pytest.raises(ShuttingDownError):
            await asked
    assert server._batcher.queue_depth == 0 and len(device.batches) == 2
    with pytest.raises(ShuttingDownError):
        await server._batcher.submit({"user": 6})


async def high_water_sheds_by_the_queues_depth(server, device):
    await take_both_slots(server, device)
    await queue_behind(server, [4, 5, 6])  # the high water of this server
    with pytest.raises(LoadShedError, match="3/3"):
        await server._batcher.submit({"user": 7})
    assert server._batcher.shed_count == 1 and server._batcher.queue_depth == 3


async def the_solo_fast_path_still_engages(server, device):
    device.auto = True
    assert await ask(server, 1) == "answer:1"
    (solo,) = device.batches
    assert solo.thread.startswith("pio-dispatch") and not solo.signalled
    await until(lambda: not server._batcher._finish_tasks)
    # and a batch of two takes the pipeline: finalize on a fetch thread, with
    # the slot's callback
    assert await asyncio.gather(ask(server, 2), ask(server, 3)) == ["answer:2", "answer:3"]
    assert device.batches[1].thread.startswith("pio-fetch") and device.batches[1].signalled


async def a_stream_of_batches_never_puts_three_ahead(server, device):
    """Slots go back from four fetch threads while the loop dispatches: with a
    short switch interval, 300 queries in bursts are all answered, never three
    batches ahead of the device, and both slots are free at the end."""
    device.auto, device.jitter = True, random.Random(25)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        asked = []
        for user in range(300):
            asked.append(ask(server, user))
            if user % 7 == 0:
                await asyncio.sleep(0.001)
        answers = await asyncio.wait_for(asyncio.gather(*asked), 30)
    finally:
        sys.setswitchinterval(interval)
    assert answers == [f"answer:{user}" for user in range(300)]
    assert len(device.batches) > 20 and device.most_ahead <= 2
    await until(lambda: not server._batcher._finish_tasks)
    assert free_slots(server) == 2


def drive(scenario, **config):
    server = make_server(**config)
    device = StubDevice()
    server._dispatch_query_batch = device

    async def body():
        try:
            await scenario(server, device)
        finally:
            for batch in device.batches:
                batch.release()
            server._batcher.close()
            await server._batcher.wait_closed()

    asyncio.run(body())
    return server


SCENARIOS = [
    (arrivals_in_the_slot_wait_ride_the_batch_in_order, {}),
    (at_max_batch_the_batch_closes_and_the_rest_stay_queued, {"max_batch_size": 4}),
    (two_ahead_and_the_third_goes_when_the_first_is_answered, {}),
    (the_slot_comes_back_once_after_a_finalize_that_raises, {}),
    (the_slot_comes_back_once_after_a_watchdog_trip, {}),
    (the_slot_comes_back_once_after_a_cancellation, {}),
    (an_expired_or_abandoned_query_is_not_dispatched, {}),
    (close_while_waiting_fails_the_queued_queries, {}),
    (high_water_sheds_by_the_queues_depth, {"queue_high_water": 3}),
    (the_solo_fast_path_still_engages, {}),
    (a_stream_of_batches_never_puts_three_ahead, {"queue_high_water": 0}),
]


@pytest.mark.parametrize(
    "scenario, config", SCENARIOS, ids=[scenario.__name__ for scenario, _ in SCENARIOS]
)
def test_the_batcher(scenario, config):
    drive(scenario, **config)


def test_the_batcher_has_no_depth_to_set():
    import inspect

    assert list(inspect.signature(_MicroBatcher.__init__).parameters) == [
        "self", "server", "max_batch", "window_s", "high_water", "shed_retry_after_s",
    ]


def test_the_slot_counters_move_as_documented():
    """``pio_batch_slot_wait_seconds_total``: the seconds the batcher waited
    for a slot with a query pending. ``pio_batch_joined_in_slot_wait_total``:
    the dispatched queries that arrived after that wait began."""

    async def scenario(server, device):
        await take_both_slots(server, device)
        scraped = metrics_of(server)
        # both slots were free when their batches came: nothing waited, nothing joined
        assert scraped["pio_batch_slot_wait_seconds_total"] < 0.01
        assert scraped["pio_batch_joined_in_slot_wait_total"] == 0.0
        await queue_behind(server, [4])  # the wait begins with one pending
        await asyncio.sleep(0.05)
        (gone,) = await queue_behind(server, [5])
        await queue_behind(server, [6, 7])
        gone.cancel()  # joined, and is not dispatched: not counted
        await asyncio.sleep(0.01)
        device.batches[0].answer.set()
        await until(lambda: server._batcher.batches_dispatched == 3)
        assert device.batches[2].users == [4, 6, 7]

    server = drive(scenario)
    scraped = metrics_of(server)
    assert 0.05 < scraped["pio_batch_slot_wait_seconds_total"] < 2.0
    assert scraped["pio_batch_joined_in_slot_wait_total"] == 2.0
    assert server._batcher.queries_dispatched == 6
