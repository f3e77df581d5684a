"""The seam between the sequential engine and its backbones: a backbone is one
module and one line of ``backbone.BACKBONES``, and nothing above that table
knows one by name. Every case is a case of each backbone in the table."""

from __future__ import annotations

import ast
import dataclasses
import importlib
import json
import re
from pathlib import Path

import pytest

from predictionio_tpu.models.sequential import engine_factory
from predictionio_tpu.models.sequential.backbone import BACKBONES
from predictionio_tpu.models.sequential.records import BackboneParams

PACKAGE = Path(importlib.import_module("predictionio_tpu.models.sequential").__file__).parent
ENGINE = "predictionio_tpu.models.sequential.engine"

# name -> (the variant file, the module, the Config the published keys give:
# written out from the variant's keys as the hand-written mappings of before
# the table gave them, not computed by the code under test)
PUBLISHED = {
    "olmoe": ("olmoe-1b-7b.json", "olmoe", dict(
        hidden_size=2048, intermediate_size=1024, num_hidden_layers=16, num_attention_heads=16,
        num_experts=64, num_experts_per_tok=8, vocab_size=50304, max_position_embeddings=4096,
        rms_norm_eps=1e-05, rope_theta=10000.0,
    )),
    "kimi_linear": ("kimi-linear-48b-a3b.json", "kimi_linear", dict(
        hidden_size=2304, intermediate_size=9216, moe_intermediate_size=1024, num_hidden_layers=8,
        num_attention_heads=32, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kda_num_heads=32, kda_head_dim=128, short_conv_kernel_size=4,
        kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26),
        full_attn_layers=(4, 8, 12, 16, 20, 24, 27), first_k_dense_replace=1, num_experts=256,
        num_experts_per_token=8, num_shared_experts=1, routed_scaling_factor=2.446, rms_norm_eps=1e-05,
        experts_held=(0, 64), vocab_slice=(0, 40960), model_max_length=1048576,
    )),
    "sdar": ("sdar-30b-a3b.json", "sdar", dict(
        hidden_size=2048, moe_intermediate_size=768, num_hidden_layers=48, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128, num_experts=128, num_experts_per_tok=8, vocab_size=151936,
        rms_norm_eps=1e-06, rope_theta=1000000.0, block_length=4, denoising_steps=4, mask_token_id=151935,
        cache_tokens=29696, most_passes=24, generated_slots=24,
    )),
    "lfm2": ("lfm2-8b-a1b.json", "lfm2", dict(
        hidden_size=2048, intermediate_size=7168, moe_intermediate_size=1792, num_hidden_layers=24,
        layer_types=("conv", "conv", "full_attention", "conv") * 5 + ("conv", "full_attention", "conv", "conv"),
        conv_L_cache=3, num_attention_heads=32, num_key_value_heads=8, num_dense_layers=2, num_experts=32,
        num_experts_per_tok=4, routed_scaling_factor=1.0, norm_eps=1e-05, rope_theta=1000000.0,
        vocab_size=65536, max_position_embeddings=128000, experts_held=(0, 8),
    )),
    "kanana": ("kanana-2-30b-a3b.json", "kanana", dict(
        hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768, num_hidden_layers=48,
        num_attention_heads=32, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense_replace=1, n_routed_experts=128, num_experts_per_tok=6, n_shared_experts=2,
        routed_scaling_factor=2.448, rms_norm_eps=1e-06, rope_theta=1000000.0, vocab_size=128256,
        max_position_embeddings=32768, cache_tokens=31744, generated_slots=32,
    )),
    "granite": ("granite-4.0-h-small.json", "granite", dict(
        hidden_size=4096, intermediate_size=768, shared_intermediate_size=1536, num_hidden_layers=10,
        layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4, num_attention_heads=32,
        num_key_value_heads=8, mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
        num_local_experts=72, num_experts_per_tok=10, attention_multiplier=0.0078125, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0, rms_norm_eps=1e-05, max_position_embeddings=131072,
        experts_held=(0, 36), vocab_slice=(0, 50176),
    )),
}

every_backbone = pytest.mark.parametrize("name", sorted(BACKBONES))


def module_of(name):
    return importlib.import_module(f"predictionio_tpu.models.sequential.{PUBLISHED[name][1]}")


def params_of(name):
    variant = json.loads((PACKAGE / "variants" / PUBLISHED[name][0]).read_text())
    ((algorithm, params),) = engine_factory().engine_params_from_variant(variant).algorithms
    assert algorithm == name
    return params


def test_the_table_is_what_this_file_knows_and_the_factory_spreads_it():
    assert sorted(BACKBONES) == sorted(PUBLISHED)
    algorithms = engine_factory().algorithm_classes
    assert {name: algorithms[name] for name in BACKBONES} == BACKBONES
    assert sorted(set(algorithms) - set(BACKBONES)) == ["attention", "markov"]


@every_backbone
def test_the_variant_file_loads_and_gives_the_modules_config_of_the_published_keys(name):
    params, module = params_of(name), module_of(name)
    assert type(params) is BACKBONES[name].params_class and isinstance(params, BackboneParams)
    # the parameters stand in the backbone's module, beside the Config they feed
    assert type(params).__module__ == module.__name__
    config = params.config()
    assert type(config) is module.Config
    assert config == module.Config(**PUBLISHED[name][2])
    assert dataclasses.asdict(config) == PUBLISHED[name][2]
    # what serving reads them by: a float stays a float whatever the file wrote
    for field in dataclasses.fields(config):
        assert type(getattr(config, field.name)) is type(PUBLISHED[name][2][field.name]), field.name
    hash(config)  # the programs' static argument


@every_backbone
def test_a_key_the_variant_leaves_out_is_the_published_default(name):
    params = params_of(name)
    variant = json.loads((PACKAGE / "variants" / PUBLISHED[name][0]).read_text())
    written = variant["algorithms"][0]["params"]
    assert set(written) <= {f.name for f in dataclasses.fields(params)}
    cut = {key: written[key] for key in ("num_hidden_layers", "experts_held", "vocab_slice", "seed") if key in written}
    variant["algorithms"][0]["params"] = cut
    ((_, bare),) = engine_factory().engine_params_from_variant(variant).algorithms
    assert bare.config() == params.config()


@every_backbone
def test_a_config_field_is_a_parameter_of_its_name_or_derived_or_the_configs_own(name):
    params, module = params_of(name), module_of(name)
    mine = {f.name for f in dataclasses.fields(params)}
    derived = set(params.derived())
    for field in dataclasses.fields(module.Config):
        own = field.default is not dataclasses.MISSING
        assert field.name in mine or field.name in derived or own, field.name
    # nothing the hook returns is lost
    assert derived <= {f.name for f in dataclasses.fields(module.Config)}


def another(value):
    if isinstance(value, bool):
        return not value
    if value is None:
        return 1
    if isinstance(value, str):
        return value + "x"
    if isinstance(value, tuple):
        return value + (1,)
    return value + 1


@every_backbone
def test_every_one_answer_key_at_another_value_is_refused_by_name(name):
    params = params_of(name)
    assert params.ONE_ANSWER and "model_type" in params.ONE_ANSWER
    for key, only in params.ONE_ANSWER.items():
        only = only(params) if callable(only) else only
        assert getattr(params, key) in (only, list(only) if isinstance(only, tuple) else only)  # the variant's own is the one answer
        wrong = dataclasses.replace(params, **{key: another(only)})
        with pytest.raises(ValueError, match=rf"^{name}: {key}=.* is not implemented \(only "):
            wrong.config()


def test_the_refusals_read_as_they_did():
    params = BACKBONES["olmoe"].params_class(norm_topk_prob=True)
    with pytest.raises(ValueError) as refused:
        params.config()
    assert str(refused.value) == "olmoe: norm_topk_prob=True is not implemented (only False)"
    with pytest.raises(ValueError, match="sdar: the key/value heads do not divide the heads"):
        BACKBONES["sdar"].params_class(num_key_value_heads=5).config()
    # a published LIST where the one answer is the empty tuple is that answer
    assert BACKBONES["sdar"].params_class(mlp_only_layers=[]).config().mask_token_id == 151935
    with pytest.raises(ValueError, match=r"sdar: mlp_only_layers=\[3\] is not implemented \(only \(\)\)"):
        BACKBONES["sdar"].params_class(mlp_only_layers=[3]).config()


@every_backbone
def test_the_model_class_names_the_module_and_a_stored_class_path_finds_it(name):
    algorithm = BACKBONES[name]
    model_class, module = algorithm.model_class, module_of(name)
    assert model_class.program() is module is model_class.module
    assert algorithm.params_class.__module__ == module.__name__
    # what a stored model's manifest says (controller.make_persistent_model) and
    # how it is found again (prepare_model): the engine's module, before and since
    path = f"{model_class.__module__}.{model_class.__qualname__}"
    assert path == f"{ENGINE}.{model_class.__name__}"
    found, _, cls = path.rpartition(".")
    assert getattr(importlib.import_module(found), cls) is model_class


def test_every_name_the_package_exports_is_the_engines():
    package = importlib.import_module("predictionio_tpu.models.sequential")
    engine = importlib.import_module(ENGINE)
    for exported in package.__all__:
        assert getattr(package, exported) is getattr(engine, exported), exported
    for other in ("session_tails", "BackboneAlgorithm", "BackboneModel", "GroupedAlgorithm", "BackboneParams"):
        assert hasattr(engine, other), other


def without_imports(source: str) -> list[str]:
    lines = source.splitlines()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for at in range(node.lineno - 1, node.end_lineno):
                lines[at] = ""
    return lines


def test_nothing_above_the_table_names_a_backbone():
    """``engine.py`` names no backbone but in its imports, and ``backbone.py``
    only from its first backbone's class on (the model classes, the algorithm
    classes, the table): the shared host side serves whatever the table holds."""
    named = re.compile("|".join(sorted({name.split("_")[0] for name in BACKBONES})), re.IGNORECASE)
    assert named.pattern == "granite|kanana|kimi|lfm2|olmoe|sdar"
    engine = without_imports((PACKAGE / "engine.py").read_text())
    assert len(engine) < 800
    assert [(at + 1, line) for at, line in enumerate(engine) if named.search(line)] == []
    source = (PACKAGE / "backbone.py").read_text()
    classes = {c.__name__ for a in BACKBONES.values() for c in (a, a.model_class)}
    table = min(n.lineno for n in ast.parse(source).body if isinstance(n, ast.ClassDef) and n.name in classes)
    shared = without_imports(source)[: table - 1]
    assert len(shared) > 400  # the shared part is there to be looked at
    assert [(at + 1, line) for at, line in enumerate(shared) if named.search(line)] == []


def test_the_records_import_no_jax_and_nothing_of_the_package_and_every_import_points_down():
    def imported(name):
        tree = ast.parse((PACKAGE / f"{name}.py").read_text())
        found = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                found |= {alias.name for alias in node.names}
            elif isinstance(node, ast.ImportFrom):
                found |= {node.module} | {f"{node.module}.{alias.name}" for alias in node.names}
        return found

    own = "predictionio_tpu.models.sequential"
    level = {"engine": 3, "backbone": 2, "records": 0, "metrics": 0, **{PUBLISHED[n][1]: 1 for n in PUBLISHED}}
    for name, height in level.items():
        for other, below in level.items():
            if f"{own}.{other}" in imported(name):
                assert below < height or (height == below == 1 and other == "olmoe"), (name, other)
    records = imported("records")
    assert not any(m.split(".")[0] in ("jax", "jaxlib") or m.startswith(own) for m in records), records
