"""Controls of ``seq-sdar-moe``'s check: faults PLANTED in the served
program, each of which the check has to refuse.

    python3 benchmark/controls_sdar.py --seed 7 [--controls experts_7,...] [--configured 0]

deploys the cell's configuration as ``benchmark/run.py`` does (the same
``Serving``: weights and sessions from the seed, the program's ``QueryServer``
in front, every program shape warmed), asks over HTTP for the users whose
replies the generators would keep, and runs ``Serving.check`` on them once as
configured (which has to count nothing wrong) and once under each control
(which has to count something). One line a check on stdout, and as the LAST
line ``{"ok": ...}``; exit 1 unless every check came out as it has to.

A control replaces a function of the program by a wrapper (``CONTROLS``) and
empties the served programs' compile caches: the SERVER then answers the same
users through the faulty program (two prefill shapes and the pass compile
again through the server's own warm-up, about half a minute a control on the
chip: under a query they would pass its deadline), and the check reads the
faulty replies. A query carries the control's name in a key the server
ignores, so that its result cache answers none of them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

START = time.monotonic()
ROOT = Path(__file__).resolve().parent.parent
WORKLOAD = "seq-sdar-moe.serve-sat"


def _fp8(x):
    """``x`` rounded to float8 e4m3's four exponent and three mantissa bits
    (XLA removes a cast pair; ``reduce_precision`` stays)."""
    from jax import lax

    return lax.reduce_precision(x, 4, 3)


def experts_7(sdar, moe, attention, config):
    """A router that sends a token to 7 experts (the 8th copy a second one of
    the 7th's, at weight 0), renormalised over the 7."""
    import jax.numpy as jnp

    plain = moe.route

    def route(x, router_w, k, renormalise=False):
        weights, experts = plain(x, router_w, k - 1, renormalise)
        return jnp.pad(weights, ((0, 0), (0, 1))), jnp.pad(experts, ((0, 0), (0, 1)), mode="edge")

    moe.route = route


def experts_fp8(sdar, moe, attention, config):
    """The experts' matrices rounded to fp8 where the grouped products read
    them (the layer's own 128 of the stacked groups: 0.4 GB at a time)."""
    import jax.numpy as jnp
    from jax import lax

    plain, e = moe.grouped_matmul, config.num_experts

    def grouped(lhs, rhs, sizes, out, **kw):
        first = jnp.argmax(sizes > 0) // e * e
        layer = _fp8(lax.dynamic_slice_in_dim(rhs, first, e, 0))
        return plain(lhs, layer, lax.dynamic_slice_in_dim(sizes, first, e, 0), out, **kw)

    moe.grouped_matmul = grouped


def not_renormalised(sdar, moe, attention, config):
    """The chosen experts' weights as the softmax gives them."""
    plain = moe.route
    moe.route = lambda x, router_w, k, renormalise=False: plain(x, router_w, k, False)


def token_causal(sdar, moe, attention, config):
    """A token-causal mask inside the block (in the prefill: the passes'
    blocks see themselves whole by what a pass is)."""
    plain = sdar.fused_attention
    sdar.fused_attention = lambda q, k, v, **kw: plain(q, k, v, **{**kw, "block": None})


def stale_cache(sdar, moe, attention, config):
    """No commit is read: later blocks see what a block's LAST DENOISE pass
    wrote, one position still masked."""
    import numpy as np

    plain = sdar.new_state
    sdar.new_state = lambda weights, config, seg, commits, *rest: plain(
        weights, config, seg, np.roll(commits, -1, axis=0), *rest
    )


def kv_fp8(sdar, moe, attention, config):
    """Keys and values rounded to fp8 where they are MADE: what the cache
    keeps and every attention reads, in the prefill and in a pass."""
    plain = sdar._queries_keys_values

    def rounded(n1, position, layer, config):
        q, k, v = plain(n1, position, layer, config)
        return q, _fp8(k), _fp8(v)

    sdar._queries_keys_values = rounded


CONTROLS = {
    "experts_7": experts_7, "experts_fp8": experts_fp8, "not_renormalised": not_renormalised,
    "token_causal": token_causal, "stale_cache": stale_cache, "kv_fp8": kv_fp8,
}
PATCHED = (
    ("sdar", "fused_attention"), ("sdar", "new_state"), ("sdar", "_queries_keys_values"), ("moe", "route"),
    ("moe", "grouped_matmul"),
)


def asked_users(deployment, engine, ctx) -> list[int]:
    """The first users the window's generators ask, one of the longest
    bucket among them."""
    import numpy as np

    users = []
    lengths = np.diff(deployment.model.offsets)
    longest = np.flatnonzero(lengths > deployment.model_config.buckets()[-2])
    if len(longest):
        users.append(int(longest[0]))
    for user in engine.stream_of(ctx, deployment.n_users).tolist():
        if len(users) > engine.CHECKED_QUERIES:
            break
        if int(user) not in users:
            users.append(int(user))
    return users


def run(root, seed: int, names, platform: str = "tpu", workload: str = WORKLOAD, out=sys.stdout) -> bool:
    """``names``: the checks to make in order, None the one as configured;
    ``platform`` and ``workload`` are the tests' (a tiny cell on the CPU)."""
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark import harness

    with harness.open_cell(root, workload, seed, 0.0, False, START, platform) as opened:
        _, ctx, engine, _, _ = opened
        from predictionio_tpu.models.sequential import sdar
        from predictionio_tpu.ops import attention, moe

        modules = {"sdar": sdar, "moe": moe}
        programs = (sdar.session_vectors, sdar.denoise_pass)
        deployment = engine.serving(ctx)
        try:
            users = asked_users(deployment, engine, ctx)
            body, ok, cold = deployment.body_format, True, False
            for name in names:
                saved = [getattr(modules[m], f) for m, f in PATCHED]
                if name is not None:
                    CONTROLS[name](sdar, moe, attention, deployment.model_config)
                    for program in programs:
                        program.clear_cache()
                    cold = True
                if cold:
                    # compiled as a deploy compiles them, not under a query's deadline
                    deployment.server._warmup()
                    cold = False
                try:
                    # (a key the server ignores: no reply comes from its result cache)
                    deployment.body_format = body[:-1] + ',"control":"%s"}' % (name or "none")
                    t = time.monotonic()
                    deployment.checked_replies = {user: deployment.ask(user) for user in users}
                    asked_s = time.monotonic() - t
                    deployment.asked_early = set()  # every reply here is the script's own
                    checked, wrong, worst = deployment.check(deployment.checked_replies)
                finally:
                    deployment.body_format = body
                    for (m, f), function in zip(PATCHED, saved):
                        setattr(modules[m], f, function)
                    if name is not None:
                        for program in programs:
                            program.clear_cache()
                        cold = True
                as_expected = (wrong == 0) if name is None else (wrong > 0)
                ok = ok and as_expected and checked >= engine.CHECKED_QUERIES // 2
                line = {
                    "control": name or "as configured", "checked": checked, "wrong": wrong,
                    "as_expected": as_expected, "worst_score_error": worst, "asked_s": asked_s,
                    **deployment.readings,
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
