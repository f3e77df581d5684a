"""chip_smoke.py and the JAX set-up function, checked without a chip.

The smoke itself passes only on a TPU (``python chip_smoke.py`` through the
chip tool). What can be held here: its parent never imports JAX, its data
is a function of the seed alone, it refuses a CPU by name, and the compile
cache lands where the contract says.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_python(code: str, env: dict[str, str], cwd: str = REPO):
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=cwd, env=env,
    )


def _env(**overrides: str | None) -> dict[str, str]:
    env = {**os.environ, "PYTHONPATH": REPO}
    for name, value in overrides.items():
        if value is None:
            env.pop(name, None)
        else:
            env[name] = value
    return env


def test_parent_plan_imports_no_jax(tmp_path):
    """A process that has touched JAX holds the chip; the parent must not
    (checked in a clean interpreter, as the lint self-test does)."""
    code = (
        "import sys, chip_smoke\n"
        f"plan = chip_smoke.build_plan(chip_smoke.parse_args([]), {str(tmp_path)!r})\n"
        "assert list(plan.steps) == ['kernels', 'app_new', 'import', 'train',"
        " 'models_show', 'deploy', 'check'], list(plan.steps)\n"
        "assert plan.env['PIO_FS_BASEDIR'].startswith(plan.workdir)\n"
        "bad = sorted(m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'jaxlib')))\n"
        "assert not bad, bad\n"
        "print('OK', plan.env['JAX_PLATFORMS'], plan.env['JAX_COMPILATION_CACHE_DIR'])\n"
    )
    proc = _clean_python(code, _env(JAX_PLATFORMS=None, JAX_COMPILATION_CACHE_DIR=None))
    assert proc.returncode == 0, proc.stderr
    # unset means the chip, and the cache inside the checkout
    assert proc.stdout.split() == ["OK", "tpu", os.path.join(REPO, ".jax_cache")]


def test_events_are_a_function_of_the_seed(tmp_path):
    import chip_smoke

    digests = []
    for name, seed in (("a", 7), ("b", 7), ("c", 8)):
        path = tmp_path / f"{name}.jsonl"
        chip_smoke.write_events(str(path), seed, n_users=300, n_items=90, n_ratings=2_000)
        digests.append(hashlib.sha256(path.read_bytes()).hexdigest())
    assert digests[0] == digests[1] != digests[2]
    users, items, vals = chip_smoke.synthesize_ratings(7, 300, 90, 2_000)
    # full width: every user and item occurs; half-star ratings in [1, 5]
    assert set(users.tolist()) == set(range(300)) and set(items.tolist()) == set(range(90))
    assert set((vals * 2).tolist()) <= set(range(2, 11))
    first = (tmp_path / "a.jsonl").read_text().splitlines()[0]
    assert first.startswith('{"event":"rate","entityType":"user","entityId":"u')


def test_the_widths_are_not_options():
    """A smoke at a toy width must not be able to end in the same result
    line as a real one: only scale (the ratings count) is settable, and
    not below one rating for each entity."""
    import chip_smoke

    for flag in ("--users", "--items", "--rank"):
        with pytest.raises(SystemExit):
            chip_smoke.parse_args([flag, "8"])
    assert chip_smoke.parse_args(["--ratings", "200000"]).ratings == 200_000
    with pytest.raises(ValueError, match="full width"):
        chip_smoke.synthesize_ratings(0, chip_smoke.N_USERS, chip_smoke.N_ITEMS, 100_000)


def test_the_result_line_has_the_contract_keys_and_no_others():
    """The chip check reads the last line of standard output and refuses
    anything but {"ok", "device": {"platform", "kind", "count"}}; what else
    a run has to say (its shape) goes on the lines before."""
    import json

    import chip_smoke

    found = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "extra": "dropped"}
    line = json.loads(json.dumps(chip_smoke.result_line(found)))
    assert line == {"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert type(line["device"]["count"]) is int


def test_refuses_the_cpu_by_name():
    """Run where JAX is held to the CPU it exits non-zero, says which
    platform it found, and prints no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=_env(JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "found platform 'cpu'" in proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout


def test_cache_dir_set_from_outside_is_left_alone(tmp_path):
    code = (
        "import os, sys\n"
        "from predictionio_tpu.utils.platform import configure_jax\n"
        "configure_jax()\n"
        "assert 'jax' not in sys.modules\n"
        "print(os.environ['JAX_COMPILATION_CACHE_DIR'], os.environ['JAX_PLATFORMS'],"
        " os.environ['JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS'])\n"
    )
    outside = str(tmp_path / "cache")
    proc = _clean_python(code, _env(JAX_COMPILATION_CACHE_DIR=outside, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    # the directory and an explicit platform stay as given; the small
    # serving programs are admitted either way
    assert proc.stdout.split() == [outside, "cpu", "0"]


def test_default_cache_dir_is_one_absolute_path(tmp_path):
    code = (
        "import os\n"
        "from predictionio_tpu.utils.platform import configure_jax\n"
        "configure_jax()\n"
        "print(os.environ.get('JAX_COMPILATION_CACHE_DIR'))\n"
    )
    env = _env(JAX_COMPILATION_CACHE_DIR=None, JAX_PLATFORMS=None)
    seen = {
        _clean_python(code, env, cwd=cwd).stdout.strip() for cwd in (REPO, str(tmp_path))
    }
    assert seen == {os.path.join(REPO, ".jax_cache")}
    # a CPU run gets no default cache (its loader's warnings, its quick compiles)
    cpu = _clean_python(code, {**env, "JAX_PLATFORMS": "cpu"})
    assert cpu.stdout.strip() == "None"
