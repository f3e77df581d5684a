#!/usr/bin/env python3
"""Are a tree's served sequence programs another tree's? Compiled for a v5e
that is described, not attached (no chip; ``JAX_PLATFORMS=cpu``), and
compared with every source location taken out.

    python scripts/served_programs_hlo.py compile ROOT OUT   # ROOT: a checkout (``git archive`` of a commit, or .)
    python scripts/served_programs_hlo.py diff OUT_A OUT_B

``compile`` writes the optimised HLO of the programs that the cells serve
whose layers call ``ops/moe`` and ``ops/attention`` (``seq-olmoe``: ``[4, 2048]``,
``[1, 2048]``, ``[1, 4096]``; ``seq-kimi-linear`` and ``seq-lfm2-moe``:
``[1, 2048]``, ``[1, 4096]``, and ``seq-granite-4-h``'s where the tree has
them (a tree from before PR 49 has no such module and the pair of trees is
compared on the rest); ``seq-sdar-moe``: a denoise pass and both
prefills), from the code
under ROOT, one file a program. ``diff`` prints, a program, the lines
that differ once locations are gone: the tables of files, functions and frames
at a module's head, and the locations INSIDE each Mosaic kernel (its body is
base64 MLIR bytecode in the custom call's ``backend_config`` and embeds the
tree's path and line numbers, so a plain text diff always shows every kernel
as changed: it is parsed and printed without debug information). 0 everywhere
says the edit left those cells' programs alone (PERF.md section 6: PR 33, 34,
41, 42 and 44 showed it so). One process a tree: only one may load libtpu.
"""
import base64
import glob
import importlib
import importlib.util
import json
import os
import re
import sys

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def compile_programs(root: str, out: str) -> None:
    root, out = os.path.abspath(root), os.path.abspath(out)
    for key, value in (
        ("TPU_LOG_DIR", "disabled"), ("JAX_PLATFORMS", "cpu"),
        ("TPU_ACCELERATOR_TYPE", "v5litepod-4"), ("TPU_WORKER_HOSTNAMES", "localhost"),
    ):
        os.environ.setdefault(key, value)
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "tests")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"  # the chip's kernels, not the CPU's stand-ins
    import test_tpu_compile as shapes  # the tree's own shapes of SDAR's state
    from predictionio_tpu.models.sequential import engine_factory, kimi_linear, lfm2, olmoe, sdar

    assert olmoe.__file__.startswith(root), olmoe.__file__

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def dump(name, compiled):
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, name + ".hlo"), "w") as f:
            f.write(compiled.as_text())
        print(name, flush=True)

    served = [
        (olmoe, "seq-olmoe", ((4, 2048), (1, 2048), (1, 4096))),
        (kimi_linear, "seq-kimi-linear", ((1, 2048), (1, 4096))),
        (lfm2, "seq-lfm2-moe", ((1, 2048), (1, 4096))),
    ]
    if importlib.util.find_spec("predictionio_tpu.models.sequential.granite"):
        granite = importlib.import_module("predictionio_tpu.models.sequential.granite")
        served.append((granite, "seq-granite-4-h", ((1, 2048), (1, 4096))))
    for backbone, cell, streams in served:
        name = backbone.__name__.rsplit(".", 1)[1]
        engine = importlib.import_module(f"benchmark.engines.sequential_{name}")
        with open(os.path.join(root, "benchmark", "configs", cell + ".json")) as f:
            variant = engine.variant_of(json.load(f), 5)
        config = engine_factory().engine_params_from_variant(variant).algorithms[0][1].config()
        weights = {n: shape(s, jnp.bfloat16) for n, s in backbone.weight_shapes(config).items()}
        for rows, length in streams:
            stream, last = shape((rows, length), jnp.int32), shape((rows, 32), jnp.int32)
            compiled = backbone.session_vectors.lower(weights, stream, stream, stream, last, config=config).compile()
            dump(f"{name}_{rows}x{length}", compiled)
    config = shapes._sdar_at_the_cell()
    weights = {n: shape(s, jnp.bfloat16) for n, s in sdar.weight_shapes(config).items()}
    cache, state = shapes._sdar_state(chip, config, weights)
    dump("sdar_pass", sdar.denoise_pass.lower(weights, state, config=config).compile())
    for length in (2048, 4096):
        stream = shape((1, length), jnp.int32)
        dump(
            f"sdar_prefill_{length}",
            sdar.session_vectors.lower(weights, cache, stream, stream, stream, shape((), jnp.int32), config=config).compile(),
        )


def _without_locations(path: str) -> list:
    from jax._src.lib.mlir import ir

    def kernel(match):
        config = json.loads(match.group(1))
        with ir.Context() as context:
            context.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(config["custom_call_config"]["body"]))
            config["custom_call_config"]["body"] = module.operation.get_asm(enable_debug_info=False)
        return "backend_config=" + json.dumps(config, sort_keys=True)

    with open(path) as f:
        text = re.sub(r"stack_frame_id=\d+", "", re.sub(r", metadata=\{[^}]*\}", "", f.read()))
    lines = [line for line in text.splitlines() if not re.match(r"^\d+ ", line) and line not in TABLES]
    return [re.sub(r"backend_config=(\{.*\})\s*$", kernel, line) if "tpu_custom_call" in line else line for line in lines]


def diff(a_dir: str, b_dir: str) -> int:
    total = 0
    for path in sorted(glob.glob(os.path.join(a_dir, "*.hlo"))):
        name = os.path.basename(path)
        a, b = _without_locations(path), _without_locations(os.path.join(b_dir, name))
        differing = sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))
        print(f"{name}: {len(a)} and {len(b)} lines, {sum('tpu_custom_call' in x for x in a)} kernels, {differing} differing")
        total += differing
    return total


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "compile":
        compile_programs(sys.argv[2], sys.argv[3])
    elif len(sys.argv) == 4 and sys.argv[1] == "diff":
        sys.exit(1 if diff(sys.argv[2], sys.argv[3]) else 0)
    else:
        sys.exit(__doc__)
