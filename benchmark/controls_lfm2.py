"""Controls of ``seq-lfm2-moe``'s check: lower precisions and faults PLANTED in
the program, each of which the check has to refuse.

    python3 benchmark/controls_lfm2.py --seed 7 [--controls weights_fp8,...] [--configured 0]

deploys the cell's configuration as ``benchmark/run.py`` does (the same
``Serving``: weights and sessions from the seed, the program's ``QueryServer``
in front, every program shape warmed), asks over HTTP, all at once so that
the batcher packs them into shared streams as the window's are, for the users
whose replies the generators would keep, and runs ``Serving.check`` on the
replies once as configured (which has to count nothing wrong) and once under
each control (which has to count something, but for ``NOT_TOLD``). One line a
check on stdout, and as the LAST line ``{"ok": ...}``; exit 1 unless every
check came out as it has to.

A control replaces a function of the program by a wrapper (``CONTROLS``) and
the served programs are traced AGAIN, through the algorithm's own
``warmup_serving`` (this backbone's compile in a quarter of a minute each; a
first query would meet the server's deadline of 10 s), so the replies are the
planted program's and the check's probes, which call the program's gated
convolution and router through their modules, meet the wrapper too.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

START = time.monotonic()
ROOT = Path(__file__).resolve().parent.parent
WORKLOAD = "seq-lfm2-moe.serve-sat"


def _bf16(x):
    from jax import lax

    return lax.reduce_precision(x, 8, 7)


def weights_fp8(lfm2, moe):
    """Every matrix of the served tree rounded to float8's THREE mantissa
    bits (e4m3's; the exponent kept whole, as a scale a tensor would keep
    every weight in range: the kindest float8) on its way into the program;
    the reference keeps the bfloat16 tree. ``reduce_precision``, not a pair
    of ``astype``: XLA drops a narrowing conversion it may exceed
    (``xla_allow_excess_precision``), and the first control so planted read
    as the sound program does (PERF.md, PR 41)."""
    import jax
    from jax import lax

    plain = lfm2.session_vectors.__wrapped__

    def rounded(a):
        return lax.reduce_precision(a, 8, 3) if a.ndim >= 2 else a

    def session_vectors(weights, *stream, config):
        return plain(jax.tree.map(rounded, weights), *stream, config=config)

    lfm2.session_vectors = jax.jit(session_vectors, static_argnames=("config",))


def router_one_pass(lfm2, moe):
    """The router's product with its operands rounded to bfloat16 (what one
    pass multiplies)."""
    plain = moe.route_sigmoid
    moe.route_sigmoid = lambda x, w, bias, k, scale, eps=0.0: plain(_bf16(x), _bf16(w), bias, k, scale, eps)


def no_expert_bias(lfm2, moe):
    """A router that chooses by the scores alone."""
    import jax.numpy as jnp

    plain = moe.route_sigmoid
    moe.route_sigmoid = lambda x, w, bias, k, scale, eps=0.0: plain(x, w, jnp.zeros_like(bias), k, scale, eps)


def no_position_mask(lfm2, moe):
    """The convolution's taps reach into whatever lies in front of a session
    in its stream. The served scores move where a session is SHORT (the two
    spoilt positions are much of it) and not in the median; the gate's probe
    lays a session twice in one row and meets it."""
    plain = lfm2.short_conv
    lfm2.short_conv = lambda x, w, tail=None, position=None, **kw: plain(x, w, tail=tail, **kw)


def gates_bf16(lfm2, moe):
    """``B * u`` and ``C * conv`` computed in bfloat16: the thirds and both
    products rounded (the taps stay float32)."""
    import jax.numpy as jnp

    def gated_conv(projected, taps, position=None):
        b, c, u = (_bf16(t) for t in jnp.split(projected, 3, axis=-1))
        y, _ = lfm2.short_conv(_bf16(b * u), taps, position=position, activation=None)
        return _bf16(c * _bf16(y))

    lfm2.gated_conv = gated_conv


CONTROLS = {
    "weights_fp8": weights_fp8, "router_one_pass": router_one_pass, "no_expert_bias": no_expert_bias,
    "no_position_mask": no_position_mask, "gates_bf16": gates_bf16,
}
NOT_TOLD: set = set()


def kept_replies(deployment, engine, ctx) -> dict:
    """Replies over HTTP for the first users the window's generators ask,
    asked for all at once: the batcher packs them as it packs a window's."""
    users = []
    for user in engine.stream_of(ctx, deployment.n_users).tolist():
        if len(users) == engine.CHECKED_QUERIES:
            break
        if int(user) not in users:
            users.append(int(user))
    # one user of the longest bucket, whom the check would else ask for alone
    longest = np.flatnonzero(np.diff(deployment.model.offsets) > deployment.model_config.buckets()[-2])
    if len(longest) and int(longest[0]) not in users:
        users.append(int(longest[0]))
    with ThreadPoolExecutor(len(users)) as pool:
        return dict(zip(users, pool.map(deployment.ask, users)))


def rewarm(deployment) -> None:
    """Every program shape compiled again as the server's start compiles it,
    outside any query's deadline."""
    server = deployment.server
    server.algorithms[0].warmup_serving(deployment.model, server.config.max_batch_size)


def run(root, seed: int, names, platform: str = "tpu", workload: str = WORKLOAD, out=sys.stdout) -> bool:
    """``names``: the checks to make in order, None the one as configured;
    ``platform`` and ``workload`` are the tests' (a tiny cell on the CPU)."""
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark import harness

    with harness.open_cell(root, workload, seed, 0.0, False, START, platform) as opened:
        _, ctx, engine, _, _ = opened
        from predictionio_tpu.models.sequential import lfm2
        from predictionio_tpu.ops import moe

        deployment = engine.serving(ctx)
        # a reply lives this long in the server's result cache: a control's
        # replies have to be its own program's
        ttl = float(deployment.server.config.result_cache_ttl_s) + 0.5
        try:
            ok, asked = True, -ttl
            for name in names:
                saved = (lfm2.session_vectors, lfm2.short_conv, lfm2.gated_conv, moe.route_sigmoid)
                if name is not None:
                    CONTROLS[name](lfm2, moe)
                    saved[0].clear_cache()
                    rewarm(deployment)
                try:
                    time.sleep(max(0.0, asked + ttl - time.monotonic()))
                    kept = kept_replies(deployment, engine, ctx)
                    asked = time.monotonic()
                    deployment.checked_replies = kept  # whom this asked for is no stranger
                    checked, wrong, worst = deployment.check(kept)
                finally:
                    lfm2.session_vectors, lfm2.short_conv, lfm2.gated_conv, moe.route_sigmoid = saved
                    if name is not None:
                        saved[0].clear_cache()
                        if name != names[-1]:
                            rewarm(deployment)  # the next check's replies are the sound program's again
                as_expected = (wrong == 0) if name is None or name in NOT_TOLD else (wrong > 0)
                ok = ok and as_expected and checked >= engine.CHECKED_QUERIES // 2
                line = {
                    "control": name or "as configured", "checked": checked, "wrong": wrong,
                    "as_expected": as_expected, "worst_score_error": worst, **deployment.readings,
                }
                print(json.dumps(line), file=out, flush=True)
        finally:
            deployment.stop()
    print(json.dumps({"ok": ok}), file=out, flush=True)
    return ok


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument(
        "--configured", type=int, choices=(0, 1), default=1,
        help="0 leaves out the check as configured (a run of the cell has made it)",
    )
    args = parser.parse_args(argv)
    names = [name for name in args.controls.split(",") if name]
    unknown = sorted(set(names) - set(CONTROLS))
    if unknown:
        parser.error(f"no such control: {unknown} (there are {sorted(CONTROLS)})")
    names = [None] * args.configured + names
    sys.path.insert(0, str(ROOT))
    from benchmark import harness

    try:
        return 0 if run(ROOT, args.seed, names) else 1
    except harness.Refused as exc:
        print(f"benchmark: refused: {exc}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
