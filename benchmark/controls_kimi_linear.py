"""Controls of ``seq-kimi-linear``'s check: lower precisions and a faulty
router PLANTED in the program, each of which the check has to refuse.

    python3 benchmark/controls_kimi_linear.py --seed 7 [--controls state_bf16,...] [--configured 0]

deploys the cell's configuration as ``benchmark/run.py`` does (the same
``Serving``: weights and sessions from the seed, the program's ``QueryServer``
in front, every program shape warmed), asks over HTTP for the users whose
replies the generators would keep, and runs ``Serving.check`` on them once as
configured (which has to count nothing wrong) and once under each control
(which has to count something, but for ``NOT_TOLD``). One line a check on
stdout, and as the LAST line ``{"ok": ...}``; exit 1 unless every check came
out as it has to.

A control replaces a function of the program by a wrapper (``CONTROLS``): the
check's probes call the program's scan and router through their modules, so
they meet the wrapper; the served programs are NOT compiled again (3 to 4 min
a control), so the replies stay the configured program's and only the probes
see the fault. What a fault does to the served scores is PERF.md's (section
6, PR 31, "The check").
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

START = time.monotonic()
ROOT = Path(__file__).resolve().parent.parent
WORKLOAD = "seq-kimi-linear.serve-sat"


def _bf16(x):
    from jax import lax

    return lax.reduce_precision(x, 8, 7)


def state_bf16(linear_attention, moe):
    """The KDA state rounded to bfloat16 wherever a chunk hands it on."""
    import jax.numpy as jnp
    from jax import lax

    plain, chunk = linear_attention.kda, linear_attention.CHUNK

    def kda(q, k, v, g, b, state=None):
        batch, length, heads, d_k = k.shape
        n = -(-length // chunk)

        def chunks(x):
            x = jnp.pad(x, [(0, 0), (0, n * chunk - length)] + [(0, 0)] * (x.ndim - 2))
            return jnp.moveaxis(x.reshape((batch, n, chunk) + x.shape[2:]), 1, 0)

        def one(s, xs):
            o, s = plain(*xs, state=s)
            return _bf16(s), o

        if state is None:
            state = jnp.zeros((batch, heads, d_k, v.shape[-1]), jnp.float32)
        # the padding (k 0, b 0, g 0) leaves the state as it is
        state, o = lax.scan(one, state, tuple(chunks(x) for x in (q, k, v, g, b)))
        return jnp.moveaxis(o, 0, 1).reshape((batch, n * chunk) + o.shape[3:])[:, :length], state

    linear_attention.kda = kda


def decay_bf16(linear_attention, moe):
    """The log decays rounded to bfloat16 before the scan. NOT told on the
    chip: it moves the scan's output by 5e-5 to 7e-5 of its size, which is
    what the configured three-pass products do to a session of 250 items
    themselves (PERF.md, PR 31); run so that its reading stands beside the
    limit. float32 against float32 tells it (tests/test_linear_attention.py)."""
    plain = linear_attention.kda
    linear_attention.kda = lambda q, k, v, g, b, state=None: plain(q, k, v, _bf16(g), b, state)


def one_pass(linear_attention, moe):
    """The scan's products with their operands rounded to bfloat16 (what one
    pass multiplies)."""
    import jax.numpy as jnp
    from jax import lax

    def dot(spec, a, b):
        return jnp.einsum(
            spec, _bf16(a), _bf16(b), precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    linear_attention._dot = dot


def experts_7(linear_attention, moe):
    """A router that sends a token to 7 experts (the 8th copy a second one of
    the 7th's, at weight 0)."""
    import jax.numpy as jnp

    plain = moe.route_sigmoid

    def route(x, router_w, bias, k, scale):
        weights, experts = plain(x, router_w, bias, k - 1, scale)
        return jnp.pad(weights, ((0, 0), (0, 1))), jnp.pad(experts, ((0, 0), (0, 1)), mode="edge")

    moe.route_sigmoid = route


def no_bias(linear_attention, moe):
    """A router that chooses by the scores alone."""
    import jax.numpy as jnp

    plain = moe.route_sigmoid
    moe.route_sigmoid = lambda x, w, bias, k, scale: plain(x, w, jnp.zeros_like(bias), k, scale)


CONTROLS = {
    "state_bf16": state_bf16, "decay_bf16": decay_bf16, "one_pass": one_pass,
    "experts_7": experts_7, "no_bias": no_bias,
}
NOT_TOLD = {"decay_bf16"}


def kept_replies(deployment, engine, ctx) -> dict:
    """Replies over HTTP for the first users the window's generators ask."""
    kept = {}
    for user in engine.stream_of(ctx, deployment.n_users).tolist():
        if len(kept) == engine.CHECKED_QUERIES:
            break
        kept.setdefault(int(user), None)
    return {user: deployment.ask(user) for user in kept}


def run(root, seed: int, names, platform: str = "tpu", workload: str = WORKLOAD, out=sys.stdout) -> bool:
    """``names``: the checks to make in order, None the one as configured;
    ``platform`` and ``workload`` are the tests' (a tiny cell on the CPU)."""
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark import harness

    with harness.open_cell(root, workload, seed, 0.0, False, START, platform) as opened:
        _, ctx, engine, _, _ = opened
        from predictionio_tpu.ops import linear_attention, moe

        deployment = engine.serving(ctx)
        try:
            kept = kept_replies(deployment, engine, ctx)
            ok = True
            for name in names:
                saved = (linear_attention.kda, linear_attention._dot, moe.route_sigmoid)
                if name is not None:
                    CONTROLS[name](linear_attention, moe)
                try:
                    checked, wrong, worst = deployment.check(kept)
                finally:
                    linear_attention.kda, linear_attention._dot, moe.route_sigmoid = saved
                # the reply of the longest bucket's user, which the check asked for
                kept = deployment.checked_replies
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
