"""Traffic kind ``open_loop_http``: Poisson arrivals at the cell's fixed
``rate_qps``, whatever the server does. The schedule of due times and users
is drawn from the seed before the window; latency runs from the time a
request was DUE, so a stall counts against every request it delays, and the
generators' own lateness is reported beside it.
"""

from __future__ import annotations

from benchmark import http_load, schedule


def measure(ctx, engine, deployment):
    due, users = schedule.open_loop_schedule(
        ctx.seed, deployment.n_users, ctx.traffic, ctx.seconds
    )
    return http_load.measure(ctx, engine, deployment, "open", users, due)


def run(ctx, engine):
    return http_load.run(ctx, engine, measure)
