"""Controls of ``seq-kanana-2``'s check: faults PLANTED in the served
program, each of which the check has to refuse.

    python3 benchmark/controls_kanana.py --seed 7 [--controls experts_5,...] [--configured 0]

deploys the cell's configuration as ``benchmark/run.py`` does (the same
``Serving``: weights and sessions from the seed, the program's ``QueryServer``
in front, every program shape warmed), asks over HTTP for the users whose
replies the generators would keep, and runs ``Serving.check`` on them once as
configured (which has to count nothing wrong) and once under each control
(which has to count something). One line a check on stdout, and as the LAST
line ``{"ok": ...}``; exit 1 unless every check came out as it has to.

A control replaces a function of the program by a wrapper (``CONTROLS``) and
empties the served programs' compile caches: the SERVER then answers the same
users through the faulty program (two prefill shapes, the first pick and the
step compile again through the server's own warm-up: under a query they would
pass its deadline), and the check reads the faulty replies. A query carries
the control's name in a key the server ignores, so that its result cache
answers none of them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

START = time.monotonic()
ROOT = Path(__file__).resolve().parent.parent
WORKLOAD = "seq-kanana-2.serve-sat"


def cache_fp8(kanana, moe, config):
    """The latent and the rotary key rounded to float8 e4m3's three mantissa
    bits where they are MADE: what the cache keeps and every attention reads,
    in the prefill and in a step."""
    from jax import lax

    plain = kanana._latent
    kanana._latent = lambda *args: lax.reduce_precision(plain(*args), 4, 3)


def stale_slots(kanana, moe, config):
    """A step attends by the slot map of BEFORE its own write: the session's
    newest position is in the cache and not seen."""
    plain = kanana._slot_ids
    kanana._slot_ids = lambda state, config: plain({**state, "made": state["made"] - 1}, config)


def step_key_unturned(kanana, moe, config):
    """A step caches its new position's rotary key as at position 0 (RoPE left
    off it); the prefill's keys and every query are turned as they should."""
    import jax.numpy as jnp

    absorbed, latent = kanana._mla_absorbed, kanana._latent

    def unturned(*args):
        kanana._latent = lambda n1, position, *rest: latent(n1, jnp.zeros_like(position), *rest)
        try:
            return absorbed(*args)
        finally:
            kanana._latent = latent

    kanana._mla_absorbed = unturned


def experts_5(kanana, moe, config):
    """A router that sends a token to 5 experts (the 6th copy a second one of
    the 5th's, at weight 0), renormalised over the 5."""
    import jax.numpy as jnp

    plain = moe.route_sigmoid

    def route(x, router_w, bias, k, scale, eps=0.0):
        weights, experts = plain(x, router_w, bias, k - 1, scale, eps)
        return jnp.pad(weights, ((0, 0), (0, 1))), jnp.pad(experts, ((0, 0), (0, 1)), mode="edge")

    moe.route_sigmoid = route


def no_bias(kanana, moe, config):
    """The experts chosen by their scores alone."""
    import jax.numpy as jnp

    plain = moe.route_sigmoid
    moe.route_sigmoid = lambda x, router_w, bias, *rest, **kw: plain(x, router_w, jnp.zeros_like(bias), *rest, **kw)


def latent_unnormalised(kanana, moe, config):
    """The latent cached as ``W_kva`` makes it, ``kv_a_layernorm`` left out:
    the prefill expands it and the steps read it as it lies."""
    import jax.numpy as jnp

    def latent(n1, position, layer, config):
        rank = config.kv_lora_rank
        both = kanana._project(n1, layer["w_kva"])
        k_r = kanana._rope_interleaved(both[..., None, rank:], position, config.rope_theta)[..., 0, :]
        return jnp.concatenate([both[..., :rank], k_r], axis=-1)

    kanana._latent = latent


CONTROLS = {
    "cache_fp8": cache_fp8, "stale_slots": stale_slots, "step_key_unturned": step_key_unturned,
    "experts_5": experts_5, "no_bias": no_bias, "latent_unnormalised": latent_unnormalised,
}
PATCHED = (
    ("kanana", "_latent"), ("kanana", "_slot_ids"), ("kanana", "_mla_absorbed"), ("moe", "route_sigmoid"),
)


def run(root, seed: int, names, platform: str = "tpu", workload: str = WORKLOAD, out=sys.stdout) -> bool:
    """``names``: the checks to make in order, None the one as configured;
    ``platform`` and ``workload`` are the tests' (a tiny cell on the CPU)."""
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark import harness
    from benchmark.controls_sdar import asked_users

    with harness.open_cell(root, workload, seed, 0.0, False, START, platform) as opened:
        _, ctx, engine, _, _ = opened
        from predictionio_tpu.models.sequential import kanana
        from predictionio_tpu.ops import moe

        modules = {"kanana": kanana, "moe": moe}
        programs = (kanana.session_vectors, kanana.first_pick, kanana.decode_step)
        deployment = engine.serving(ctx)
        try:
            users = asked_users(deployment, engine, ctx)
            body, ok, cold = deployment.body_format, True, False
            for name in names:
                saved = [getattr(modules[m], f) for m, f in PATCHED]
                if name is not None:
                    CONTROLS[name](kanana, moe, deployment.model_config)
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
