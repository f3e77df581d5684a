"""Traffic kind ``closed_loop_http``: a fixed pool of keep-alive connections,
each sending its next request when the reply is in (a web tier's connection
pool, a re-scoring job). Capacity with no growing queue and no refusals: the
end-to-end metric is the correct answers received per second of the window.
"""

from __future__ import annotations

from benchmark import http_load, schedule


def measure(ctx, engine, deployment):
    users = schedule.closed_loop_users(
        ctx.seed, deployment.n_users, ctx.traffic, int(ctx.traffic["users_drawn"])
    )
    return http_load.measure(ctx, engine, deployment, "closed", users)


def run(ctx, engine):
    return http_load.run(ctx, engine, measure)
