"""The open-loop schedule and the load generator: the same seed gives the same
traffic, latency runs from the time a request was DUE, lateness is reported."""

import importlib.util
import json
import socket
import threading
import time

import numpy as np
import pytest

from benchmark_testkit import REPO

from benchmark import schedule

TRAFFIC = {"rate_qps": 500.0, "ramp_s": 1.0, "user_zipf_exponent": 0.6}


def test_same_seed_same_schedule_other_seed_another():
    due_a, users_a = schedule.open_loop_schedule(7, 100_000, TRAFFIC, 10.0)
    due_b, users_b = schedule.open_loop_schedule(7, 100_000, TRAFFIC, 10.0)
    due_c, users_c = schedule.open_loop_schedule(8, 100_000, TRAFFIC, 10.0)
    assert np.array_equal(due_a, due_b) and np.array_equal(users_a, users_b)
    assert len(due_a) != len(due_c) or not np.array_equal(due_a, due_c)
    assert np.array_equal(
        schedule.closed_loop_users(7, 100_000, TRAFFIC, 500),
        schedule.closed_loop_users(7, 100_000, TRAFFIC, 500),
    )


def test_schedule_is_poisson_at_the_rate_with_the_ramp_before_zero():
    due, users = schedule.open_loop_schedule(3, 100_000, TRAFFIC, 20.0)
    assert due[0] >= -1.0 and due[-1] < 20.0 and np.all(np.diff(due) > 0)
    assert len(due) == len(users)
    assert abs(len(due) / 21.0 - 500.0) < 500.0 * 0.05
    gaps = np.diff(due)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05  # exponential gaps
    assert users.min() >= 0 and users.max() < 100_000


def test_zipf_users_skew_follows_the_exponent():
    rng = np.random.default_rng(0)
    users = schedule.zipf_users(rng, 1000, 1.0, 200_000)
    counts = np.sort(np.bincount(users, minlength=1000))[::-1]
    # rank 1 over rank 10 is 10 at exponent 1; the permutation hides which user is which
    assert 8.0 < counts[0] / counts[9] < 12.5
    flat = schedule.zipf_users(rng, 1000, 0.0, 200_000)
    assert np.bincount(flat, minlength=1000).min() > 120


def _loadgen():
    spec = importlib.util.spec_from_file_location("benchmark_loadgen", REPO / "benchmark" / "loadgen.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


BODY = json.dumps({"itemScores": [{"item": f"i{i}", "score": 1.0} for i in range(3)]}).encode()


@pytest.fixture
def slow_server():
    """Keep-alive HTTP on a raw socket: every reply takes 50 ms, and one
    connection is served at a time."""
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(8)
    stop = threading.Event()

    def serve(conn):
        with conn:
            buf = b""
            while not stop.is_set():
                chunk = conn.recv(65536)
                if not chunk:
                    return
                buf += chunk
                while b"\r\n\r\n" in buf:
                    head, _, rest = buf.partition(b"\r\n\r\n")
                    length = int(head.lower().split(b"content-length:")[1].split(b"\r\n")[0])
                    if len(rest) < length:
                        break
                    buf = rest[length:]
                    time.sleep(0.05)
                    conn.sendall(
                        b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\nContent-Length: "
                        + str(len(BODY)).encode() + b"\r\n\r\n" + BODY
                    )

    def accept():
        listener.settimeout(0.2)
        while not stop.is_set():
            try:
                conn, _ = listener.accept()
            except OSError:
                continue
            threading.Thread(target=serve, args=(conn,), daemon=True).start()

    thread = threading.Thread(target=accept, daemon=True)
    thread.start()
    yield listener.getsockname()[1]
    stop.set()
    thread.join(timeout=2)
    listener.close()


def _spec(port, **more):
    return {
        "port": port, "path": "/queries.json", "body_format": '{"user":"u%d","num":3}',
        "item_marker": '"item"', "items_expected": 3, "timeout_s": 2.0,
        "t0": time.monotonic() + 0.2, "users": [5, 6, 7, 8], **more,
    }


def test_open_loop_latency_runs_from_the_due_time_and_lateness_is_reported(slow_server):
    # four requests due 10 ms apart on ONE connection to a server that takes
    # 50 ms a reply: each waits for the one before it
    gen = _loadgen().Generator(
        _spec(slow_server, mode="open", connections=1, due=[0.0, 0.01, 0.02, 0.03], keep=[2])
    )
    out = gen.run()
    assert out["ok"] == [1, 1, 1, 1] and out["index"] == [0, 1, 2, 3]
    due, sent, done = (np.asarray(out[k]) for k in ("due", "sent", "done"))
    assert np.allclose(due, [0.0, 0.01, 0.02, 0.03], atol=1e-6)
    late = sent - due
    assert late[0] < 0.02 and late[3] > 0.1  # the fourth went out three replies late
    from_due, from_send = done - due, done - sent
    assert from_due[3] > 0.15 > from_send[3] > 0.045  # the stall counts against it
    assert json.loads(out["kept"]["2"])["itemScores"][0]["item"] == "i0"
    assert out["connects"] == 1


def test_closed_loop_sends_the_next_request_when_the_reply_is_in(slow_server):
    gen = _loadgen().Generator(
        _spec(slow_server, mode="closed", connections=2, start_s=0.0, stop_s=0.5)
    )
    out = gen.run()
    sent, done = np.asarray(out["sent"]), np.asarray(out["done"])
    assert 12 <= len(sent) <= 22 and all(out["ok"])  # two callers, 50 ms a reply, 0.5 s
    assert np.allclose(np.asarray(out["due"]), sent)  # a closed loop's request is due when sent
    assert np.all(done - sent > 0.045)


def test_a_reply_with_too_few_items_or_another_status_is_failed(slow_server):
    gen = _loadgen().Generator(
        {**_spec(slow_server, mode="open", connections=1, due=[0.0]), "items_expected": 10}
    )
    out = gen.run()
    assert out["ok"] == [0] and "3 items" in out["errors"][0]


def test_the_generator_never_imports_jax():
    text = (REPO / "benchmark" / "loadgen.py").read_text()
    assert "import jax" not in text and "import numpy" not in text
