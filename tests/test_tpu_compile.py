"""The main path's kernels compiled for the chip, with no chip: the TPU
compiler is installed here and compiles for a v5e that is described, not
attached. It refuses what interpret mode lets through (a tile over the
VMEM limit, a slice off the tiling, a program over the device's memory),
so these guard every later edit at no chip time. Nothing runs: no result
and no time comes from here.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports every test file.
Keep every such compile in THIS file."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# rank and systems: the template's default over ML-20M's items, the
# benchmark's rank over its users, rank 64 (one 128-lane row a tile), and
# ALX's rank over a quarter of the users (the raised VMEM limit)
@pytest.mark.parametrize(
    "f, n", [(10, 26_745), (32, 138_494), (64, 138_494), (128, 34_624)]
)
def test_the_cg_tile_kernel_compiles_for_v5e(one_chip, f, n):
    from predictionio_tpu.ops.spd_solve import _cg_tiles

    compiled = (
        jax.jit(_cg_tiles)
        .lower(_shape(one_chip, (f, f, n)), _shape(one_chip, (f, n)))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_als_step_at_the_benchmark_shape_holds_its_systems_unpadded(
    one_chip, monkeypatch
):
    """``_als_step`` at ML-20M, rank 32, as the chip traces it: the solve
    is the kernel, and the temporaries are 3.5 GB (7.1 GB while the CG read
    ``[n, f, f]`` padded to 128 lanes: PERF.md section 4)."""
    from predictionio_tpu.ops import als

    # code that asks for the backend sees the CPU here; the chip's branch
    # is steered from the test, not by an option of the program
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_users, n_items, d, nb_u, nb_i, f = 138_493, 26_744, 128, 258_304, 172_160, 32
    tables = lambda nb: (
        _shape(one_chip, (nb,), jnp.int32),
        _shape(one_chip, (nb, d), jnp.int32),
        _shape(one_chip, (nb, d)),
        _shape(one_chip, (nb, d), jnp.int8),
    )
    compiled = als._als_step.lower(
        _shape(one_chip, (n_users + 1, f)), _shape(one_chip, (n_items + 1, f)),
        *tables(nb_u), *tables(nb_i),
        n_users=n_users, n_items=n_items, reg=0.05, implicit=False, alpha=1.0,
        block_chunk=16384 // d, degree_scaled_reg=True, solver="cg",
        gather_dtype="f32",
    ).compile()
    text = compiled.as_text()
    assert "solve/pallas_call" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4.0e9


@pytest.mark.parametrize("bucket", [8, 32, 128])
def test_the_serve_program_reads_its_bfloat16_item_table_as_it_lies(one_chip, bucket):
    """``_serve_by_index_batch`` at the webgraph cells' shape with the item
    table as ``ServingIndex`` stores it on the chip: the ``[n, f]`` bfloat16
    table goes into the product's fusion as it lies (a copy, a transpose or
    a convert of it would cost 1.46 GB and more a batch: PERF.md section 6,
    PR 40), the scores are float32, and the temporaries are the scores."""
    import re

    from predictionio_tpu.ops import topk

    n, f = 5_700_000, 128
    compiled = topk._serve_by_index_batch.lower(
        _shape(one_chip, (bucket,), jnp.int32),
        _shape(one_chip, (n, f)),
        _shape(one_chip, (n, f), jnp.bfloat16),
        _shape(one_chip, (n,), jnp.bool_),
        k=16,
    ).compile()
    text = compiled.as_text()
    whole_table = rf"= \w+\[({n},{f}|{f},{n})\]\S* (copy|transpose|convert)\("
    assert not re.findall(whole_table, text)
    (product,) = re.findall(r"= (\w+)\[\d+,\d+\]\S* convolution\(", text)
    assert product == "f32"
    assert compiled.memory_analysis().temp_size_in_bytes <= bucket * n * 4 * 1.01


# latent attention expanded for a prefill: keys of 192, values of 128; the
# tiled kernel over a packed stream at the Kimi-Linear cell's two program
# shapes, and the single-block kernel (L under 1,024) and the tiled one
# without a segment
@pytest.mark.parametrize(
    "rows, length, packed", [(1, 2048, True), (1, 4096, True), (32, 64, False), (2, 1024, False)]
)
def test_fused_attention_with_values_narrower_than_keys_compiles_for_v5e(
    one_chip, monkeypatch, rows, length, packed
):
    from predictionio_tpu.ops.attention import fused_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = _shape(one_chip, (rows, 32, length, 192), jnp.bfloat16)
    narrow = _shape(one_chip, (rows, 32, length, 128), jnp.bfloat16)
    segment = (_shape(one_chip, (rows, length), jnp.int32),) if packed else ()

    def attend(q, k, v, *segment):
        return fused_attention(q, k, v, causal=True, segment=segment[0] if segment else None)

    compiled = jax.jit(attend).lower(wide, wide, narrow, *segment).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.output_shardings is not None
    assert jax.eval_shape(attend, wide, wide, narrow, *segment).shape == (rows, 32, length, 128)


# OLMoE's 16 heads of 128 over a packed stream, at both stream lengths
@pytest.mark.parametrize("length", [2048, 4096])
def test_fused_attention_over_a_packed_stream_compiles_for_v5e(one_chip, monkeypatch, length):
    from predictionio_tpu.ops.attention import fused_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    heads = _shape(one_chip, (1, 16, length, 128), jnp.bfloat16)
    compiled = (
        jax.jit(lambda q, k, v, segment: fused_attention(q, k, v, causal=True, segment=segment))
        .lower(heads, heads, heads, _shape(one_chip, (1, length), jnp.int32))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("length", [2048, 4096])
def test_the_chunked_kda_scan_compiles_for_v5e_at_the_published_heads(one_chip, length):
    from predictionio_tpu.ops.linear_attention import CHUNK, kda

    wide = _shape(one_chip, (1, length, 32, 128))
    compiled = (
        jax.jit(lambda q, k, v, g, b, starts: kda(q, k, v, g, b, starts=starts))
        .lower(
            wide, wide, wide, wide, _shape(one_chip, (1, length, 32)),
            _shape(one_chip, (1, length // CHUNK), jnp.bool_),
        )
        .compile()
    )
    # a stream's scan keeps its temporaries under a GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# (the layout, tokens): all the copies, or the compact block of the held ones,
# 8,192 rows in tiles of 64 (16,384 in tiles of 128 at 4,096 tokens), in rounds
# under one `while_loop`: the same three grouped products a layer either way
@pytest.mark.parametrize("compact, tokens", [(False, 2048), (True, 2048), (True, 4096)])
def test_a_share_of_the_experts_compiles_for_v5e_with_the_grouped_kernel(one_chip, monkeypatch, compact, tokens):
    from predictionio_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hidden, width = 2304, 1024
    assert moe.held_block(tokens, 8, 64, 256) == (4 * tokens, tokens // 32)

    def share(x, router, bias, gate, up, down):
        weights, experts = moe.route_sigmoid(x, router, bias, 8, 2.446)
        if compact:
            return moe.held_expert_ffn(x, weights, experts, gate, up, down, held=(64, 64, 256))
        return moe.expert_ffn(x, weights, experts, gate, up, down, held=(64, 64))

    compiled = jax.jit(share).lower(
        _shape(one_chip, (tokens, hidden)), _shape(one_chip, (hidden, 256), jnp.bfloat16),
        _shape(one_chip, (256,), jnp.bfloat16), _shape(one_chip, (64, hidden, width), jnp.bfloat16),
        _shape(one_chip, (64, hidden, width), jnp.bfloat16), _shape(one_chip, (64, width, hidden), jnp.bfloat16),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


# the benchmark's check of the Kimi-Linear cell runs beside the served model
# (7.9 GB of 16.9): one session of 4,096 items through a layer of each kind
@pytest.mark.parametrize("layer, probed", [(1, True), (2, False), (4, False)])
def test_the_kimi_cells_check_keeps_a_session_of_4096_under_a_gigabyte(one_chip, layer, probed):
    """The reference takes a layer as it is served and upcasts an expert and
    a head at a time: 0.91 GB of temporaries with the scan's probe, 0.34 and
    0.37 without. (A whole layer in float32 is 2.0 GB more, and all 32
    heads' [4,096, 4,096] scores at once 2.45 GB: PERF.md, PR 31.)"""
    import json
    from pathlib import Path

    from benchmark.engines import sequential_kimi_linear as engine
    from predictionio_tpu.models.sequential import engine_factory, kimi_linear

    config = json.loads((Path(engine.__file__).parents[1] / "configs" / "seq-kimi-linear.json").read_text())
    params = engine_factory().engine_params_from_variant(engine.variant_of(config, 5)).algorithms[0][1]
    weights = jax.eval_shape(lambda: kimi_linear.init_weights(params.config(), 5))
    served = {name: _shape(one_chip, a.shape, a.dtype) for name, a in kimi_linear.layer_of(weights, layer).items()}
    shapes = {key: config[key] for key in engine.PUBLISHED + ("experts_held", "vocab_slice", "published")}
    compiled = engine.layer_step(shapes).lower(
        _shape(one_chip, (4096, config["hidden_size"])), served, _shape(one_chip, (), jnp.int32),
        like=layer, probed=probed,
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.0e9
    # nothing of the layer is copied in: the arguments are the served arrays
    assert memory.argument_size_in_bytes < 1.1e9


# SDAR's 32 query heads over 4 key/value heads of 128 under a block-causal
# mask, over a packed stream at both stream lengths (the prefill's kernel)
@pytest.mark.parametrize("length", [2048, 4096])
def test_grouped_query_block_causal_attention_compiles_for_v5e(one_chip, monkeypatch, length):
    from predictionio_tpu.ops.attention import fused_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    queries = _shape(one_chip, (1, 32, length, 128), jnp.bfloat16)
    keys = _shape(one_chip, (1, 4, length, 128), jnp.bfloat16)

    def attend(q, k, v, segment):
        return fused_attention(q, k, v, causal=True, segment=segment, block=4)

    compiled = jax.jit(attend).lower(queries, keys, keys, _shape(one_chip, (1, length), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert jax.eval_shape(attend, queries, keys, keys, jax.ShapeDtypeStruct((1, length), jnp.int32)).shape == queries.shape


def _config_at_the_cell(engine_name: str, cell: str):
    """The backbone's configuration as the benchmark's engine builds it from
    the cell's configuration file."""
    import importlib
    import json
    from pathlib import Path

    from predictionio_tpu.models.sequential import engine_factory

    engine = importlib.import_module(f"benchmark.engines.{engine_name}")
    config = json.loads((Path(engine.__file__).parents[1] / "configs" / f"{cell}.json").read_text())
    return engine_factory().engine_params_from_variant(engine.variant_of(config, 5)).algorithms[0][1].config()


def _sdar_at_the_cell():
    return _config_at_the_cell("sequential_sdar", "seq-sdar-moe")


def _sdar_state(one_chip, config, weights):
    from predictionio_tpu.models.sequential import sdar

    sessions, slots, vocabulary = sdar.SESSIONS, config.generated_slots, config.vocab_size
    a_layer = _shape(
        one_chip, (config.num_key_value_heads, config.cache_slots, config.head_dim), weights["wk"].dtype
    )
    cache = tuple(tuple(a_layer for _ in range(config.num_hidden_layers)) for _ in "kv")
    whole = lambda shape, dtype=jnp.int32: _shape(one_chip, shape, dtype)  # noqa: E731
    return cache, {
        "cache": cache, "seg": whole((config.cache_tokens,)), "commits": whole((config.most_passes, config.chunk)),
        "pass": whole(()), "tokens": whole((sessions, slots)), "step": whole((sessions, slots)),
        "logp": whole((sessions, slots), jnp.float32), "block": whole((sessions,)), "tick": whole((sessions,)),
        "blocks": whole((sessions,)), "reach": whole((sessions,)), "start": whole((sessions,)),
        "allowed": whole((sessions, vocabulary), jnp.bool_), "busiest": whole(()), "reached": whole(()),
    }


@pytest.mark.parametrize("program", ["a pass", "a prefill of 2,048", "a prefill of 4,096"])
def test_the_sdar_cells_programs_compile_for_v5e_beside_the_model(one_chip, monkeypatch, program):
    """``seq-sdar-moe``'s three programs at the published widths, six layers:
    the arguments are the served model (8.7 GB) and the batch's cache (0.4
    GB, donated and handed back in place), the temporaries under 1.5 GB (0.1
    a pass, 0.7 and 1.3 a prefill), and every kernel is there (attention a layer, three grouped products
    a sparse layer)."""
    from predictionio_tpu.models.sequential import sdar

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = _sdar_at_the_cell()
    weights = {
        name: _shape(one_chip, shape, jnp.bfloat16) for name, shape in sdar.weight_shapes(config).items()
    }
    cache, state = _sdar_state(one_chip, config, weights)
    if program == "a pass":
        compiled = sdar.denoise_pass.lower(weights, state, config=config).compile()
        kernels = 4 * config.num_hidden_layers
    else:
        length = 2048 if "2,048" in program else 4096
        stream = _shape(one_chip, (1, length), jnp.int32)
        compiled = sdar.session_vectors.lower(
            weights, cache, stream, stream, stream, _shape(one_chip, (), jnp.int32), config=config
        ).compile()
        kernels = 4  # the layers but the last are one scanned body
    memory = compiled.memory_analysis()
    assert compiled.as_text().count("tpu_custom_call") >= kernels
    assert memory.temp_size_in_bytes < 1.5e9
    assert 8.4e9 < memory.argument_size_in_bytes < 9.3e9  # (a prefill reads no `lm_head`)
    # the cache is updated where it lies
    assert memory.alias_size_in_bytes >= config.cache_bytes(config.cache_slots)


# the scorers' STACKED programs (four token streams as the rows of one, PR 38:
# what ``olmoe`` serves, and what ``kimi_linear`` takes though its engine sends
# one row) at their cells' widths: (the benchmark's engine, its configuration,
# the most the temporaries may take, the kernels a layer body holds: OLMoE's
# layers are one scanned body, Kimi-Linear's unrolled)
STACKED = {
    "olmoe": ("sequential_olmoe", "seq-olmoe", 1.5e9, 4),
    "kimi_linear": ("sequential_kimi_linear", "seq-kimi-linear", 2.5e9, 2 + 3 * 7),
}


@pytest.mark.parametrize("backbone", list(STACKED))
def test_the_scorers_stacked_programs_compile_for_v5e_beside_the_model(one_chip, monkeypatch, backbone):
    """``session_vectors`` over ``[4, TOKEN_BUDGET]`` tokens, the tallest
    program of ``olmoe``'s closed set: the served weights are its arguments
    and its temporaries (the experts' copies of every row's tokens) stay a
    small part of what the model leaves of the chip's 16.9 GB."""
    import importlib
    import json
    from pathlib import Path

    from predictionio_tpu.models.sequential import engine_factory

    engine_name, cell, temporaries, kernels = STACKED[backbone]
    engine = importlib.import_module(f"benchmark.engines.{engine_name}")
    program = importlib.import_module(f"predictionio_tpu.models.sequential.{backbone}")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    file = json.loads((Path(engine.__file__).parents[1] / "configs" / f"{cell}.json").read_text())
    config = engine_factory().engine_params_from_variant(engine.variant_of(file, 5)).algorithms[0][1].config()
    weights = {
        name: _shape(one_chip, shape, jnp.bfloat16) for name, shape in program.weight_shapes(config).items()
    }
    rows, budget = 4, program.TOKEN_BUDGET
    assert program.STACKED_ROWS in (1, rows) and config.stream_shapes()[0] == budget
    stream = _shape(one_chip, (rows, budget), jnp.int32)
    last = _shape(one_chip, (rows, budget // program.SESSION_ALIGN), jnp.int32)
    compiled = program.session_vectors.lower(weights, stream, stream, stream, last, config=config).compile()
    memory = compiled.memory_analysis()
    assert compiled.as_text().count("tpu_custom_call") >= kernels
    assert memory.temp_size_in_bytes < temporaries
    assert 6.5e9 < memory.argument_size_in_bytes < 8e9
    vectors, _ = jax.eval_shape(
        lambda *a: program.session_vectors(*a, config=config), weights, stream, stream, stream, last
    )
    assert vectors.shape == (rows * (budget // program.SESSION_ALIGN), config.hidden_size)


@pytest.mark.parametrize("length", [2048, 4096])
def test_lfm2s_whole_depth_compiles_for_v5e_beside_the_model(one_chip, monkeypatch, length):
    """``lfm2.session_vectors`` at ``seq-lfm2-moe``'s widths, all 24 layers,
    at both lengths of its closed set: the 22 sparse layers are two scans of
    7 bodies, so TWO attention kernels at a head width of 64 (32 query heads
    over 8) and 7 × (3 + 3) grouped products at a width of 1,792 (the held
    copies' compact block and, behind a ``cond``, the whole path) under the
    column tile ``ops/moe.COLUMN_TILES`` gives (1,792 whole asks for 16.96 MB
    of scoped VMEM of 16 and is refused): 44 kernels where the unrolled
    program had 72; the served weights are its arguments, 5.05 GB, and no
    expert matrix is copied on its way to a kernel."""
    import json
    from pathlib import Path

    from benchmark.engines import sequential_lfm2 as engine
    from predictionio_tpu.models.sequential import engine_factory, lfm2

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    file = json.loads((Path(engine.__file__).parents[1] / "configs" / "seq-lfm2-moe.json").read_text())
    config = engine_factory().engine_params_from_variant(engine.variant_of(file, 5)).algorithms[0][1].config()
    weights = {name: _shape(one_chip, shape, jnp.bfloat16) for name, shape in lfm2.weight_shapes(config).items()}
    assert lfm2.STACKED_ROWS == 1 and config.stream_shapes() == (2048, 4096)
    stream = _shape(one_chip, (1, length), jnp.int32)
    last = _shape(one_chip, (1, lfm2.TOKEN_BUDGET // lfm2.SESSION_ALIGN), jnp.int32)
    compiled = lfm2.session_vectors.lower(weights, stream, stream, stream, last, config=config).compile()
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2 + 7 * (3 + 3)
    # a scan's experts reach the kernels where they lie: nothing copies, slices or converts a stack of them
    # in HBM (the compiler's own prefetch into VMEM, `S(1)`, is not one)
    stacks = re.compile(
        r"= bf16\[(8|16|32),(2048,1792|1792,2048)\]\{2,1,0:T\(8,128\)\(2,1\)\} (copy|fusion|dynamic-slice|slice|convert)\("
    )
    assert not [line for line in text.splitlines() if stacks.search(line)]
    assert memory.temp_size_in_bytes < 2.0e9
    assert 5.0e9 < memory.argument_size_in_bytes < 5.1e9

def _compiled_scan(one_chip, length, p, chunk, heads=128, n=128):
    """``ops/linear_attention.ssd`` as the chip would be served it, compiled
    for v5e at granite-4.0-h-small's 128 heads over a state of 128, one
    stream under session ids."""
    from predictionio_tpu.ops.linear_attention import ssd

    args = (
        _shape(one_chip, (1, length, heads, p)), _shape(one_chip, (1, length, heads)), _shape(one_chip, (heads,)),
        _shape(one_chip, (1, length, n)), _shape(one_chip, (1, length, n)), _shape(one_chip, (heads,)),
    )
    segment = _shape(one_chip, (1, length), jnp.int32)
    compiled = jax.jit(lambda *a, segment: ssd(*a, segment=segment, chunk=chunk)).lower(*args, segment=segment).compile()
    y, state = jax.eval_shape(lambda *a: ssd(*a, chunk=chunk), *args)
    assert y.shape == (1, length, heads, p) and state.shape == (1, heads, p, n)
    return compiled


def _triangle(chunk, heads=128):
    """The XLA form's decays, a head, chunk and pair of positions."""
    return re.compile(rf"f32\[[0-9,]*{heads},{chunk},{chunk}\]")


@pytest.mark.parametrize(("chunk", "p"), [(64, 64), (128, 48), (256, 48)])
def test_the_chip_is_served_the_xla_form_of_the_scan_where_the_kernel_does_not_tile(one_chip, monkeypatch, chunk, p):
    """Shapes ``ssd_tiles`` refuses, a chunk of 64 at the published head and
    heads of 48 channels at the chunks it takes, compile ON THE CHIP to the XLA
    form, no Mosaic call: the triangles grow with the chunk (its temporaries
    stay well under a gigabyte at the widest)."""
    from predictionio_tpu.ops.linear_attention import ssd_tiles

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not ssd_tiles(128, p, 128, chunk)
    compiled = _compiled_scan(one_chip, 2048, p, chunk)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and _triangle(chunk).search(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize(("length", "chunk"), [(2048, 128), (4096, 128), (2048, 256)])
def test_the_state_space_scan_is_one_kernel_on_the_chip_at_the_shapes_it_tiles(one_chip, monkeypatch, length, chunk):
    """On the chip ``ops/linear_attention.ssd`` at the shapes ``ssd_tiles``
    takes (the published head, 64 channels over a state of 128, at
    ``granite.SSD_CHUNK`` and at ``ssd``'s own default chunk) is ONE Mosaic
    call with none of the XLA form's triangles beside it. The predicate takes
    what is compiled here and nothing else: not another head, state or chunk,
    nor heads that are no whole blocks of 16."""
    from predictionio_tpu.models.sequential.granite import SSD_CHUNK
    from predictionio_tpu.ops.linear_attention import ssd_tiles

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert SSD_CHUNK == 128 and ssd_tiles(128, 64, 128, chunk)
    refused = [(128, 48, 128, chunk), (128, 16, 128, chunk), (128, 128, 128, chunk), (128, 64, 64, chunk)]
    refused += [(128, 64, 256, chunk), (128, 64, 128, 64), (128, 64, 128, 512), (8, 64, 128, chunk)]
    assert not any(ssd_tiles(*shape) for shape in refused)
    text = _compiled_scan(one_chip, length, 64, chunk).as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1 and not _triangle(chunk).search(text)


@pytest.mark.parametrize("length", [2048, 4096])
def test_granites_period_compiles_for_v5e_beside_the_model(one_chip, monkeypatch, length):
    """``granite.session_vectors`` at ``seq-granite-4-h``'s widths, ten
    layers unrolled, at both lengths of its closed set: ONE attention kernel
    at 32 query heads over 8 of 128, nine state-space scans (one kernel a
    Mamba-2 layer, PR 51) and 10 x 3 grouped products over 36 held
    experts 768 wide with hidden 4,096 as the contraction, over the block of
    the held copies (half the router is held: all the copies' rows, 20,480
    and 40,960, in tiles of 256, every held group from a tile's edge; the
    overflow in rounds of the same body, no second path) and NO re-layout of
    the combine's gathered rows (ten copies a token lie in no float32 tile:
    they are gathered copy by copy); the served weights are its
    arguments, 9.51 GB, and its temporaries leave room for a second batch
    and the float32 table on a chip of 16.9 GB."""
    import json
    from pathlib import Path

    from benchmark.engines import sequential_granite as engine
    from predictionio_tpu.models.sequential import engine_factory, granite
    from predictionio_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.held_block(length, 10, 36, 72) == (10 * length, 256)
    file = json.loads((Path(engine.__file__).parents[1] / "configs" / "seq-granite-4-h.json").read_text())
    config = engine_factory().engine_params_from_variant(engine.variant_of(file, 5)).algorithms[0][1].config()
    weights = {name: _shape(one_chip, shape, jnp.bfloat16) for name, shape in granite.weight_shapes(config).items()}
    assert granite.STACKED_ROWS == 1 and config.stream_shapes() == (2048, 4096)
    stream = _shape(one_chip, (1, length), jnp.int32)
    last = _shape(one_chip, (1, granite.TOKEN_BUDGET // granite.SESSION_ALIGN), jnp.int32)
    compiled = granite.session_vectors.lower(weights, stream, stream, stream, last, config=config).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1 + 10 * 3 + 9
    assert f"f32[{length},10,4096]" not in text and f"f32[10,{length},4096]" in text
    assert memory.temp_size_in_bytes < (1.6e9 if length == 2048 else 3.0e9)
    assert 9.5e9 < memory.argument_size_in_bytes < 9.6e9



@pytest.mark.parametrize("slots", [512, 32768])
def test_absorbed_latent_attention_compiles_for_v5e_with_the_values_cut_out_of_the_keys(one_chip, monkeypatch, slots):
    """A step's attention at the published widths: 32 sessions' 32 heads as
    1,024 rows of ONE head 576 wide against a cache of ``slots`` whose values
    are the keys' first 512 columns (``value_width``): the single-block kernel
    (512 slots) and the tiled one (32,768) take the 4.5 lane tiles whole and
    read ONE key/value operand."""
    from predictionio_tpu.ops.attention import fused_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    queries, keys = _shape(one_chip, (1, 1, 1024, 576), jnp.bfloat16), _shape(one_chip, (1, 1, slots, 576), jnp.bfloat16)
    ids = lambda n: _shape(one_chip, (1, n), jnp.int32)  # noqa: E731

    def attend(q, k, ids_q, ids_k):
        return fused_attention(q, k, None, segment=(ids_q, ids_k), value_width=512)

    compiled = jax.jit(attend).lower(queries, keys, ids(1024), ids(slots)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    assert jax.eval_shape(attend, queries, keys, ids(1024), ids(slots)).shape == (1, 1, 1024, 512)
    # the queries, the cache and the ids are all it is given: no second operand of values
    assert compiled.memory_analysis().argument_size_in_bytes < 2 * (1024 + slots) * 576 + 8 * (1024 + slots) + 4096


@pytest.mark.parametrize("program", ["a step", "the first pick", "a prefill of 2,048", "a prefill of 4,096"])
def test_the_kanana_cells_programs_compile_for_v5e_beside_the_model(one_chip, monkeypatch, program):
    """``seq-kanana-2``'s four programs at the published widths, six layers:
    the arguments are the served model (7.58 GB; a prefill reads no
    ``lm_head``) and the group's latent cache (0.23 GB, donated and handed
    back in place), the temporaries under a gigabyte (printed), and every
    kernel is there: attention a layer (192 / 128 in the prefill, the last
    layer's by the single-block kernel; 576 / 512 in a step) and three grouped
    products a sparse layer."""
    from predictionio_tpu.models.sequential import kanana

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = _config_at_the_cell("sequential_kanana", "seq-kanana-2")
    weights = {name: _shape(one_chip, shape, jnp.bfloat16) for name, shape in kanana.weight_shapes(config).items()}
    whole = lambda shape, dtype=jnp.int32: _shape(one_chip, shape, dtype)  # noqa: E731
    sessions, layers = kanana.SESSIONS, config.num_hidden_layers
    cache = (
        tuple(whole((config.cache_slots, config.latent_width), jnp.bfloat16) for _ in range(layers)),
        whole((2 * sessions, config.hidden_size), jnp.float32),
    )
    state = {
        "cache": cache, "seg": whole((config.cache_tokens,)), "length": whole((sessions,)), "num": whole((sessions,)),
        "made": whole((sessions,)), "items": whole((sessions, config.generated_slots)),
        "logp": whole((sessions, config.generated_slots), jnp.float32),
        "allowed": whole((sessions, config.vocab_size), jnp.bool_), "busiest": whole(()), "reached": whole(()),
    }
    kernels = layers + 3 * config.sparse_layers
    if program == "a step":
        compiled = kanana.decode_step.lower(weights, state, config=config).compile()
    elif program == "the first pick":
        compiled, kernels = kanana.first_pick.lower(weights, state, config=config).compile(), 0
    else:
        length = 2048 if "2,048" in program else 4096
        stream = whole((1, length))
        compiled = kanana.session_vectors.lower(
            weights, cache, stream, stream, stream, whole((1, sessions)), whole(()), whole(()), config=config
        ).compile()
    memory = compiled.memory_analysis()
    print(f"{program}: {memory.temp_size_in_bytes / 1e9:.3f} GB of temporaries, {memory.argument_size_in_bytes / 1e9:.3f} of arguments")
    assert compiled.as_text().count("tpu_custom_call") >= kernels
    assert memory.temp_size_in_bytes < 1.0e9
    if program != "the first pick":
        assert 7.0e9 < memory.argument_size_in_bytes < 8.0e9
    # the cache is updated where it lies
    assert memory.alias_size_in_bytes >= config.cache_bytes(config.cache_slots)
