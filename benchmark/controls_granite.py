"""Controls of ``seq-granite-4-h``'s check: lower precisions and faults PLANTED
in the program, each of which the check has to refuse.

    python3 benchmark/controls_granite.py --seed 7 [--controls weights_fp8,...] [--configured 0]

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
``warmup_serving`` (this backbone's compile in half a minute each; a first
query would meet the server's deadline of 10 s), so the replies are the
planted program's and the check's probes, which call the program's
convolution, scan, attention and router through their modules, meet the
wrapper too.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

START = time.monotonic()
ROOT = Path(__file__).resolve().parent.parent
WORKLOAD = "seq-granite-4-h.serve-sat"


def _bf16(x):
    from jax import lax

    return lax.reduce_precision(x, 8, 7)


def _replanted(granite, wrap):
    """``granite.session_vectors`` jitted anew around ``wrap(weights, config)
    -> (weights, config)``."""
    import jax

    plain = granite.session_vectors.__wrapped__

    def session_vectors(weights, *stream, config):
        weights, config = wrap(weights, config)
        return plain(weights, *stream, config=config)

    granite.session_vectors = jax.jit(session_vectors, static_argnames=("config",))


def weights_fp8(granite, moe):
    """Every matrix of the served tree rounded to float8's THREE mantissa
    bits (e4m3's; the exponent kept whole, as a scale a tensor would keep
    every weight in range: the kindest float8) on its way into the program;
    the reference keeps the bfloat16 tree. ``reduce_precision``, not a pair
    of ``astype``: XLA drops a narrowing conversion it may exceed
    (``controls_lfm2.weights_fp8``)."""
    import jax
    from jax import lax

    def rounded(a):
        return lax.reduce_precision(a, 8, 3) if a.ndim >= 2 else a

    _replanted(granite, lambda weights, config: (jax.tree.map(rounded, weights), config))


def no_residual_multiplier(granite, moe):
    """``h + f(norm(h))``: ``residual_multiplier`` left out of both halves of
    every layer."""
    _replanted(granite, lambda weights, config: (weights, dataclasses.replace(config, residual_multiplier=1.0)))


def state_bf16(granite, moe):
    """The scan's state rounded to bfloat16 wherever a chunk of
    ``granite.SSD_CHUNK`` positions hands it on: the plain scan a chunk at a
    time through its ``state=``, which this wrapper zeroes itself where a
    chunk's first position begins another session than the one that ended
    the chunk before (``ssd`` hands a given state to the row's first session)."""
    import jax.numpy as jnp
    from jax import lax

    plain = granite.ssd

    def ssd(x, dt, a, b, c, d=None, state=None, segment=None, chunk=256):
        batch, length, heads, p = x.shape
        n = -(-length // chunk)

        def chunks(v, fill=0):
            v = jnp.pad(v, [(0, 0), (0, n * chunk - length)] + [(0, 0)] * (v.ndim - 2), constant_values=fill)
            return jnp.moveaxis(v.reshape((batch, n, chunk) + v.shape[2:]), 1, 0)

        seg = jnp.zeros((batch, length), jnp.int32) if segment is None else segment

        def one(carry, xs):
            s, last = carry
            x_c, dt_c, b_c, c_c, seg_c = xs
            s = jnp.where((seg_c[:, 0] == last)[:, None, None, None], s, 0.0)
            y, s = plain(x_c, dt_c, a, b_c, c_c, d, state=s, segment=seg_c, chunk=chunk)
            return (_bf16(s), seg_c[:, -1]), y

        if state is None:
            state = jnp.zeros((batch, heads, p, b.shape[-1]), jnp.float32)
        # the padding (dt 0) leaves the state as it is
        (state, _), y = lax.scan(
            one, (state, seg[:, 0]), (chunks(x), chunks(dt), chunks(b), chunks(c), chunks(seg, -1))
        )
        return jnp.moveaxis(y, 0, 1).reshape((batch, n * chunk) + y.shape[3:])[:, :length], state

    granite.ssd = ssd


def no_session_reset(granite, moe):
    """The scan WITHOUT its session ids: a session reads the state of whatever
    lies in front of it in its stream."""
    plain = granite.ssd
    granite.ssd = lambda *args, segment=None, **kw: plain(*args, **kw)


def no_position_mask(granite, moe):
    """The convolution's taps reach into whatever lies in front of a session
    in its stream."""
    plain = granite.short_conv
    granite.short_conv = lambda x, w, tail=None, position=None, **kw: plain(x, w, tail=tail, **kw)


def router_no_renorm(granite, moe):
    """The chosen experts weigh by the softmax over ALL 72 as it is, not over
    their own sum."""
    plain = moe.route
    moe.route = lambda x, w, k, renormalise=False: plain(x, w, k, False)


def scale_rsqrt_d(granite, moe):
    """The attention's scores scaled by ``head_dim ** -0.5`` (what
    ``fused_attention`` does to a ``q`` that nobody scaled), not by
    ``attention_multiplier``: the program's own factor taken out of ``q``
    again."""
    plain = granite.fused_attention

    def fused_attention(q, k, v, **kw):
        # the program multiplied q by attention_multiplier * sqrt(d): 1/128 and 128 here
        return plain((q.astype("float32") * q.shape[-1] ** 0.5).astype(q.dtype), k, v, **kw)

    granite.fused_attention = fused_attention


CONTROLS = {
    "weights_fp8": weights_fp8, "state_bf16": state_bf16, "no_session_reset": no_session_reset,
    "no_position_mask": no_position_mask, "no_residual_multiplier": no_residual_multiplier,
    "router_no_renorm": router_no_renorm, "scale_rsqrt_d": scale_rsqrt_d,
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
    outside any query's deadline. What the device holds before it goes to
    stderr: a planted program is warmed beside the deployment."""
    import jax

    memory = jax.local_devices()[0].memory_stats() or {}
    print(
        f"benchmark: warming the programs again with {memory.get('bytes_in_use', 0) / 1e9:.2f} GB in use and "
        f"{memory.get('bytes_reserved', 0) / 1e9:.2f} reserved of {memory.get('bytes_limit', 0) / 1e9:.2f}",
        file=sys.stderr, flush=True,
    )
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
        from predictionio_tpu.models.sequential import granite
        from predictionio_tpu.ops import moe

        deployment = engine.serving(ctx)
        # a reply lives this long in the server's result cache: a control's
        # replies have to be its own program's
        ttl = float(deployment.server.config.result_cache_ttl_s) + 0.5
        try:
            ok, asked = True, -ttl
            for name in names:
                saved = (granite.session_vectors, granite.ssd, granite.short_conv, granite.fused_attention, moe.route)
                if name is not None:
                    CONTROLS[name](granite, moe)
                    saved[0].clear_cache()
                    rewarm(deployment)
                try:
                    time.sleep(max(0.0, asked + ttl - time.monotonic()))
                    kept = kept_replies(deployment, engine, ctx)
                    asked = time.monotonic()
                    deployment.checked_replies = kept  # whom this asked for is no stranger
                    checked, wrong, worst = deployment.check(kept)
                finally:
                    granite.session_vectors, granite.ssd, granite.short_conv, granite.fused_attention, moe.route = saved
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
