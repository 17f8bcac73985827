"""AOT compiles for a DESCRIBED TPU v5e: what interpret mode cannot show.

Every Pallas kernel ``veles_tpu/ops/`` exports, at the widths the
``chip_smoke.py`` phases run them, plus the AlexNet fused step and the
LM decode programs, compiled by the TPU's own compiler for a ``v5e:2x2``
topology that is described and not attached.  A kernel that passes every
interpret-mode parity test can still be refused here — a block that breaks
the (8, 128) rule, a cast Mosaic does not implement, a scratch that
overflows VMEM — and was, until PR 21 (flash forward/backward, chunk
attention, the PRNG fill).

Nothing runs: a compile that passes is not a chip run.

The topology is described inside the module-scoped ``topo`` fixture and
nowhere else (only one process at a time may load the TPU's library: a
call at import would make xdist workers collect different tests), every
compile happens in this test's own process, and this stays ONE file.
"""

import functools

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.sharding import SingleDeviceSharding

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "skip"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % exc)
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> a ShapeDtypeStruct on the first described
    chip; ``chip.tree(shapes)`` places a whole pytree of structs."""
    sharding = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    struct.tree = lambda tree: jax.tree.map(
        lambda leaf: struct(leaf.shape, leaf.dtype), tree)
    return struct


# -- the cases: name -> (build(chip) -> Lowered, expects a Pallas kernel) ----

def _flash_fwd(d, chip):
    from veles_tpu.ops import attention
    q = chip((8, 2048, 8, d), bf16)
    return jax.jit(lambda q, k, v: attention._flash_fwd(
        q, k, v, causal=True)).lower(q, q, q)


def _flash_bwd(d, chip):
    from veles_tpu.ops.attention import flash_attention
    q = chip((8, 2048, 8, d), bf16)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, None,
                               True).astype(f32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)


def _chunk_attention(chip):
    from veles_tpu.ops.attention import chunk_attention
    q, kv = chip((1, 256, 16, 64), bf16), chip((1, 2048, 16, 64), bf16)
    return jax.jit(lambda q, k, v, start: chunk_attention(
        q, k, v, start, use_pallas=True, interpret=False)).lower(
            q, kv, kv, chip((), i32))      # start is TRACED


def _decode(rows, chip):
    from veles_tpu.ops import attention
    fn = attention.decode_attention if rows == 1 \
        else attention.verify_attention
    q, kv = chip((8, rows, 16, 64), bf16), chip((8, 2048, 16, 64), bf16)
    return jax.jit(lambda q, k, v, lengths: fn(
        q, k, v, lengths, use_pallas=True, interpret=False)).lower(
            q, kv, kv, chip((8,), i32))


def _paged_decode(chip):
    from veles_tpu.ops.attention import paged_decode_attention
    q, pool = chip((8, 1, 16, 64), bf16), chip((1025, 16, 16, 64), bf16)
    return jax.jit(lambda q, k, v, tables, lengths: paged_decode_attention(
        q, k, v, tables, lengths, use_pallas=True, interpret=False)).lower(
            q, pool, pool, chip((8, 128), i32), chip((8,), i32))


def _gemm(chip):
    from veles_tpu.ops.gemm import _matmul_pallas
    a = chip((4096, 4096), bf16)
    return _matmul_pallas.lower(a, a, None)


def _gemm_int8(chip):
    from veles_tpu.ops.qgemm import _qmatmul_pallas
    return _qmatmul_pallas.lower(
        chip((256, 4096), bf16), chip((4096, 4096), jnp.int8),
        chip((4096,), f32), None)


def _gd_fused(batch, fan_in, neurons, chip):
    from veles_tpu.ops.gemm import gd_fused_pallas
    x, y = chip((batch, fan_in), f32), chip((batch, neurons), f32)
    w, b = chip((fan_in, neurons), f32), chip((neurons,), f32)

    def fn(x, y, err, w, b, vw, vb):
        return gd_fused_pallas(x, y, err, w, b, vw, vb, 0.03, 0.03, 5e-4,
                               0.0, 0.9, 0.9, activation="tanh")

    return jax.jit(fn).lower(x, y, y, w, b, w, b)


def _resident(chip, rows, sample_shape, dtype):
    """A resident set in its device form (``ops.gather``), described."""
    from veles_tpu.ops.gather import ResidentRows, resident_shape
    return ResidentRows(chip((rows,) + resident_shape(sample_shape), dtype),
                        sample_shape)


def _grouped(k, n, relu2, chip):
    """The hybrid cell's expert products: 1024 tokens x 22 pairs sorted
    into 128 held experts' groups."""
    from veles_tpu.ops.grouped import grouped_matmul
    return jax.jit(lambda rows, weights, sizes: grouped_matmul(
        rows, weights, sizes, relu2=relu2,
        out_dtype=bf16 if relu2 else f32, use_pallas=True,
        interpret=False)).lower(
            chip((22528, k), bf16), chip((128, k, n), bf16),
            chip((128,), i32))


def _expert_mix(rows, k, f, held, matrices, chip):
    """A decode step's experts at the two serving cells' sizes: all
    rows through the touched experts, the result kept in fast memory."""
    from veles_tpu.ops.grouped import expert_mix
    return jax.jit(lambda x, weights, load, back, *into: expert_mix(
        x, weights, list(into), back, load, use_pallas=True,
        interpret=False)).lower(
            chip((rows, k), bf16), chip((rows, held), f32),
            chip((held,), i32), chip((held, f, k), bf16),
            *[chip((held, k, f), bf16)] * matrices)


def _prng_fill(chip):
    from veles_tpu.ops.random import _uniform_pallas_tpu
    return _uniform_pallas_tpu.lower(chip((), i32), shape=(4096, 4096))


def _alexnet_step(chip):
    """The fused AlexNet train step chip_smoke.py's ``alexnet_train``
    runs: batch 256, u8 input normalized in-step, bf16 compute, f32
    master weights.  The chip's ratings DB disables the space-to-depth
    conv rewrite; the CPU this test runs on has no DB row and would
    take the heuristic's other branch, so the test pins the chip's."""
    from veles_tpu.config import root
    from veles_tpu.samples import alexnet
    from veles_tpu.znicz.fused_graph import lower_specs
    saved = root.common.engine.get("s2d_conv", "auto")
    root.common.engine.s2d_conv = False
    try:
        params, step_fn, _eval, _apply = lower_specs(
            alexnet.LAYERS, alexnet.INPUT_SHAPE, compute_dtype=bf16,
            input_norm=(numpy.float32(1.0 / 255.0), numpy.float32(0.0)))
        return jax.jit(step_fn, donate_argnums=(0,)).lower(
            chip.tree(jax.tree.map(jnp.asarray, params)),
            chip((256,) + tuple(alexnet.INPUT_SHAPE), jnp.uint8),
            chip((256,), i32))
    finally:
        root.common.engine.s2d_conv = saved


def _lm_decode(paged, chip):
    """``samples.transformer.CONFIG``'s ONE decode-step program as the
    GenerativeEngine compiles it (8 slots, 2048 positions, bf16, cache
    donated), from ``jax.eval_shape`` shapes — no weights exist."""
    from veles_tpu.gen import TransformerGenModel
    from veles_tpu.samples import transformer
    # on the chip auto dispatch takes the Pallas decode kernels; here
    # jax.devices() is the CPU, so the test asks for them by name
    model = TransformerGenModel(transformer.CONFIG, compute_dtype=bf16,
                                use_pallas=True)
    params = chip.tree(transformer.param_shapes(transformer.CONFIG, bf16))
    slots = chip((8,), i32)
    active = chip((8,), jnp.bool_)
    if paged:
        cache = chip.tree(jax.eval_shape(functools.partial(
            model.init_paged_cache, 8 * 128 + 1, 16)))
        args = (params, cache, chip((8, 128), i32), slots, slots, active)
        fn = model.paged_decode
    else:
        cache = chip.tree(jax.eval_shape(functools.partial(
            model.init_cache, 8, 2048)))
        args = (params, cache, slots, slots, active)
        fn = model.decode
    return jax.jit(fn, donate_argnums=(1,)).lower(*args)


CASES = {
    "flash_fwd_d64": functools.partial(_flash_fwd, 64),
    "flash_fwd_d128": functools.partial(_flash_fwd, 128),
    "flash_bwd_d64": functools.partial(_flash_bwd, 64),
    "flash_bwd_d128": functools.partial(_flash_bwd, 128),
    "chunk_attention_traced_start": _chunk_attention,
    "decode_attention": functools.partial(_decode, 1),
    "verify_attention_5_rows": functools.partial(_decode, 5),
    "paged_decode_attention_block16": _paged_decode,
    "gemm_4096_bf16": _gemm,
    "gemm_int8": _gemm_int8,
    "gd_fused_4096x4096_f32": functools.partial(_gd_fused, 256, 4096,
                                                4096),
    "gd_fused_mnist_784x100": functools.partial(_gd_fused, 100, 784, 100),
    "prng_fill": _prng_fill,
    "grouped_matmul_1024x2688_relu2": functools.partial(
        _grouped, 1024, 2688, True),
    "grouped_matmul_2688x1024": functools.partial(
        _grouped, 2688, 1024, False),
    "expert_mix_relu2_64_rows_128_of_1024x2688": functools.partial(
        _expert_mix, 64, 1024, 2688, 128, 1),
    "expert_mix_relu2_a_256_bucket": functools.partial(
        _expert_mix, 256, 1024, 2688, 128, 1),
    "expert_mix_gated_24_rows_16_of_4096x4096": functools.partial(
        _expert_mix, 24, 4096, 4096, 16, 2),
    "lm_config_decode_step": functools.partial(_lm_decode, False),
    "lm_config_paged_decode_step": functools.partial(_lm_decode, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pallas_kernel_compiles_for_v5e(case, chip):
    compiled = CASES[case](chip).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "%s compiled for the v5e without a Pallas kernel in it" % case


#: name -> (rows, sample shape, storage dtype, minibatch)
RESIDENT_SETS = {
    "alexnet_cell_u8_25856x227x227x3": (25856, (227, 227, 3), jnp.uint8,
                                        256),
    "cifar_f32_50000x32x32x3": (50000, (32, 32, 3), f32, 128),
}


def _set_sized(compiled, rows):
    """The operations of a compiled program whose RESULT has the whole
    set's rows (its parameters apart), and its temporaries in bytes."""
    import re
    ops = [line.strip() for line in compiled.as_text().splitlines()
           if re.search(r"= \w+\[%d," % rows, line)
           and " parameter(" not in line]
    return ops, compiled.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("head", ["take_rows", "take_rows_norm"])
@pytest.mark.parametrize("name", sorted(RESIDENT_SETS))
def test_loader_gather_reads_the_rows_it_takes_not_the_set(name, head,
                                                           chip):
    """The minibatch gather as the loader compiles it, over the set in
    its rows-major device form: no operation whose result has the set's
    rows, temporaries under three minibatches.  And over the set in its
    own shape, as it was held before: the chip lays an image-shaped
    array out with the SAMPLES innermost, so the same gather first
    copies the whole set, and the same reading says so."""
    from veles_tpu.ops import gather
    rows, sample_shape, dtype, batch = RESIDENT_SETS[name]
    if head == "take_rows":
        take, out_dtype = jax.jit(gather.take_rows), dtype
    else:
        take, out_dtype = jax.jit(lambda data, idx: gather.take_rows_norm(
            data, idx, (1.0 / 255.0, 0.0))), f32
    minibatch = batch * int(numpy.prod(sample_shape)) \
        * jnp.dtype(out_dtype).itemsize
    idx = chip((batch,), i32)
    formed = take.lower(_resident(chip, rows, sample_shape, dtype),
                        idx).compile()
    assert "tpu_custom_call" not in formed.as_text()    # XLA's gather
    ops, temporaries = _set_sized(formed, rows)
    assert ops == [] and temporaries < 3 * minibatch, (ops, temporaries)
    today = take.lower(chip((rows,) + sample_shape, dtype), idx).compile()
    ops, temporaries = _set_sized(today, rows)
    assert ops and temporaries > 3 * minibatch, (ops, temporaries)
    assert any(" copy(" in op for op in ops)


def test_resident_form_is_written_in_place_a_chunk_at_a_time(chip):
    """The upload's one program: a chunk of the host's rows is padded,
    split into lanes and written into the donated form, which is never
    on the device twice."""
    from veles_tpu.ops import gather
    form = _resident(chip, 25856, (227, 227, 3), jnp.uint8).form
    chunk = chip((1664, 227 * 227 * 3), jnp.uint8)
    assert chunk.size <= gather.CHUNK_BYTES
    compiled = gather._place.lower(form, chunk, chip((), i32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == form.size
    assert mem.temp_size_in_bytes < 3 * chunk.size


def test_alexnet_fused_step_compiles_for_v5e_and_fits(chip, topo):
    """No Pallas kernel rides this program (conv and fc are XLA's): the
    claim is that the whole batch-256 bf16 step compiles for one v5e
    and fits its HBM."""
    from veles_tpu.backends import device_hbm_bytes
    compiled = _alexnet_step(chip).compile()
    hbm = device_hbm_bytes(topo.devices[0].device_kind)
    assert hbm == 16 << 30, \
        "device kind %r must resolve to the v5e row" \
        % topo.devices[0].device_kind
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < hbm, "AlexNet step needs %d bytes of %d" % (used,
                                                                  hbm)


def _calls(text, kernel):
    """The custom calls of a compiled program that run ``kernel``."""
    return [line for line in text.splitlines()
            if "custom-call(" in line and kernel in line]


def _reading(text, shapes, kernel="veles_expert_mix"):
    """The instructions of a compiled program, its parameters and the
    calls of ``kernel`` apart, with an operand or a result of one of
    ``shapes``: the held experts' whole arrays."""
    return [line for line in text.splitlines()
            if any(shape in line for shape in shapes)
            and kernel not in line and " parameter(" not in line
            and not line.startswith(("HloModule ", "ENTRY "))]


def _hybrid_program(which, chip):
    """The benchmark's hybrid configuration as the GenerativeEngine
    compiles it: 64 slots, 2048 positions, bf16, the cache donated."""
    import json
    import os

    from benchmarks.drivers import serve_hybrid
    from veles_tpu.gen import HybridGenModel
    from veles_tpu.samples import hybrid_lm
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "nemotron3_super_120b_a12b.json")
    with open(path) as handle:
        config = json.load(handle)
    pcfg = serve_hybrid.program_config(config)
    # the host here is a CPU: ask for the TPU's kernels
    model = HybridGenModel(pcfg, compute_dtype=bf16, use_pallas=True)
    params = chip.tree(hybrid_lm.param_shapes(pcfg, bf16))
    slots = config["engine"]["max_slots"]
    cache = chip.tree(jax.eval_shape(functools.partial(
        model.init_cache, slots, config["engine"]["max_seq"])))
    if which == "decode":
        args = (params, cache, chip((slots,), i32), chip((slots,), i32),
                chip((slots,), jnp.bool_))
        fn = model.decode
    else:
        args = (params, cache, chip((1, which), i32), chip((), i32),
                chip((), i32))
        fn = model.prefill
    return model, jax.jit(fn, donate_argnums=(1,)).lower(*args)


@pytest.mark.parametrize("which", ["decode", 256, 1024])
def test_hybrid_programs_compile_for_v5e_fit_and_write_in_place(
        which, chip, topo):
    """Mamba-2 + latent experts + grouped-query attention at published
    widths, one chip's share: the program fits the v5e's HBM beside its
    9.3 GB of weights, the whole 1.5 GB cache (recurrent state, the
    convolution's tails, K and V) is aliased in to out, the long
    prefill alone takes the grouped expert product (the kernel, two
    calls a layer, whose float32 hidden array is never written), and a
    decode step and a short bucket take the touched experts' kernel,
    one call a layer and the only reader of the held experts."""
    from veles_tpu.backends import device_hbm_bytes
    model, lowered = _hybrid_program(which, chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cache_bytes = model.cache_nbytes(64, 2048)
    assert 1.4e9 < cache_bytes < 1.6e9
    assert mem.alias_size_in_bytes == cache_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 10.5e9 < used < 15e9 < device_hbm_bytes(
        topo.devices[0].device_kind)
    assert mem.temp_size_in_bytes < 0.6e9
    text = compiled.as_text()
    assert len(_calls(text, "veles_grouped_matmul")) == (
        2 * model.pattern.count("E") if which == 1024 else 0)
    assert len(_calls(text, "veles_expert_mix")) == (
        0 if which == 1024 else model.pattern.count("E"))
    assert "ragged-dot" not in text
    assert "f32[22528,2688]" not in text
    if which == "decode":
        assert not _reading(text, ("bf16[128,1024,2688]",
                                   "bf16[128,2688,1024]"))


def _window_moe_program(which, chip):
    """The benchmark's window / full configuration as the
    GenerativeEngine compiles it: 24 slots, 32,768 positions, bf16, a
    chunk of 1024, the cache tree donated."""
    import json
    import os

    from benchmarks.drivers import serve_window_moe
    from veles_tpu.gen import WindowMoEGenModel
    from veles_tpu.samples import window_moe_lm
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "command_a_plus_05_2026.json")
    with open(path) as handle:
        config = json.load(handle)
    pcfg = serve_window_moe.program_config(config)
    engine = config["engine"]
    # the host here is a CPU: ask for the TPU's kernels
    model = WindowMoEGenModel(pcfg, compute_dtype=bf16, use_pallas=True)
    params = chip.tree(window_moe_lm.param_shapes(pcfg, bf16))
    slots = engine["max_slots"]
    cache = chip.tree(jax.eval_shape(functools.partial(
        model.init_cache, slots, engine["max_seq"])))
    if which == "decode":
        args = (params, cache, chip((slots,), i32), chip((slots,), i32),
                chip((slots,), jnp.bool_))
        fn = model.decode
    else:
        args = (params, cache, chip((1, engine["prefill_chunk"]), i32),
                chip((), i32), chip((), i32), chip((), i32))
        fn = model.prefill_chunk
    return model, engine, jax.jit(fn, donate_argnums=(1,)).lower(*args)


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_window_moe_programs_compile_for_v5e_fit_and_write_in_place(
        which, chip, topo):
    """Window and full attention layers, a parallel block and gated
    experts at published widths, one chip's share: both programs fit
    the v5e's HBM at 24 slots beside 9.5 GB of weights, the whole 4.4
    GB cache tree (three rings of 4096 rows and one layer of 32,768 a
    slot) is aliased in to out, and the chunk alone takes the grouped
    expert product (the kernel, three calls a layer: gate, up, down) and
    the chunk attention that reads the rings and the full layer in
    place (the kernel, one call a layer); a decode step takes the
    touched experts' kernel, one call a layer and the only reader of
    the held experts."""
    from veles_tpu.backends import device_hbm_bytes
    model, engine, lowered = _window_moe_program(which, chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    slots, max_seq = engine["max_slots"], engine["max_seq"]
    assert (slots, max_seq, engine["prefill_chunk"]) == (24, 32768, 1024)
    cache_bytes = model.cache_nbytes(slots, max_seq)
    assert cache_bytes == slots * (3 * 4096 + 32768) * 2 * 1024 * 2
    assert mem.alias_size_in_bytes == cache_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 13.8e9 < used < 15.6e9 < device_hbm_bytes(
        topo.devices[0].device_kind)
    assert mem.temp_size_in_bytes < (0.1e9 if which == "decode"
                                     else 0.6e9)
    text = compiled.as_text()
    assert len(_calls(text, "veles_grouped_matmul")) == (
        3 * len(model.pattern) if which == "chunk" else 0)
    assert len(_calls(text, "veles_attn_ring_chunk")) == (
        len(model.pattern) if which == "chunk" else 0)
    assert len(_calls(text, "veles_expert_mix")) == (
        0 if which == "chunk" else len(model.pattern))
    assert "ragged-dot" not in text
    if which == "decode":
        assert not _reading(text, ("bf16[16,4096,4096]",))
