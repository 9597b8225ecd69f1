"""The serving kernels (paged attention, the held experts' rows matmul)
compiled for the chip, without the chip.

The Pallas interpreter (tests/test_serving_spec.py) holds the kernel's
numbers; it cannot see what Mosaic refuses. Every refusal met while the
kernel was written showed only here: a bf16 compare on the v5e's VPU, an
ambient float32 matmul precision on bf16 operands, a DMA of a slab whose
minor dimension is not whole 128-lane rows (the int8 scales), scoped VMEM at
wide query tiles, and (PR 34) it takes a wait as done whatever it names, where
the chip counts the bytes. Nor can it see a layout: a pool kept `[NB, bs, KVH, D]` is
tiled `T(4,128)(2,1)` on the v5e, the kernel's `[NB, bs, KVH*D]` operand
`T(8,128)(2,1)`, and the reshape between them was a copy of the whole pool, K
and V, a layer a launch (42% of a decode cell's device time, PERF.md). So the
serving shapes are compiled against a described v5e
(`jax.experimental.topologies`), about a second each. A compile is not a run:
numbers and times come from the chip (PERF.md).

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports every test file.
Keep such tests in this one file.
"""
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu import serving_cache as sc
from paddle_tpu.ops.pallas import paged_attention as pk
from paddle_tpu.serving import PagedLlamaDecodeEngine


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable cannot be read back from the cache without a chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


YI, MISTRAL = (32, 4, 128), (32, 8, 128)      # heads, KV heads, head_dim
CMDA = (128, 8, 128)      # Command A+: n_rep 16, twice the widest before it
# id: (S, T, (H, KVH, D), block_size, num_blocks, max_blocks, pool, q, int8
#      [, bounded]): bounded = a window layer's call, with each row's first
# visible position. A 512-row chunk of 128 heads reaches the kernel as 8
# slots of 64 rows (serving_cache.paged_attention splits it).
SHAPES = {
    "cmda_full_decode": (32, 1, CMDA, 16, 12288, 1024, "bfloat16", "bfloat16", False),
    "cmda_window_decode": (32, 1, CMDA, 16, 9472, 1024, "bfloat16", "bfloat16", False, True),
    "cmda_full_chunk_512": (8, 64, CMDA, 16, 12288, 1024, "bfloat16", "bfloat16", False),
    "cmda_window_chunk_512": (8, 64, CMDA, 16, 9472, 1024, "bfloat16", "bfloat16", False, True),
    "cmda_window_chunk_8": (1, 8, CMDA, 16, 9472, 1024, "bfloat16", "bfloat16", False, True),
    "yi_decode_bounded": (32, 1, YI, 16, 4096, 128, "bfloat16", "bfloat16", False, True),
    "yi_decode_32_slots": (32, 1, YI, 16, 4096, 128, "bfloat16", "bfloat16", False),
    "yi_prefill_chunk_8": (1, 8, YI, 16, 4096, 128, "bfloat16", "bfloat16", False),
    "yi_prefill_chunk_64": (1, 64, YI, 16, 4096, 128, "bfloat16", "bfloat16", False),
    "yi_verify_window_5": (32, 5, YI, 16, 4096, 128, "bfloat16", "bfloat16", False),
    "yi_int8_pool_decode": (32, 1, YI, 16, 4096, 128, "int8", "bfloat16", True),
    "yi_int8_pool_chunk_64": (1, 64, YI, 16, 4096, 128, "int8", "bfloat16", True),
    "mistral_decode": (32, 1, MISTRAL, 16, 4096, 128, "bfloat16", "bfloat16", False),
    "float32_pool_decode": (8, 1, YI, 16, 1024, 128, "float32", "float32", False),
    "dense_engine_tile_128": (4, 1, YI, 128, 64, 16, "bfloat16", "bfloat16", False),
    "dense_engine_prompt_256": (4, 256, YI, 128, 64, 16, "bfloat16", "bfloat16", False),
    "two_token_blocks": (2, 1, YI, 2, 64, 128, "bfloat16", "bfloat16", False),
    # a slot's first group is started by the slot before it (PR 34): an odd
    # number of slots, and a window layer's decode call (narrow slots: waits
    # by binary digit, half a group's starts inside the arithmetic)
    "yi_decode_33_slots": (33, 1, YI, 16, 4096, 128, "bfloat16", "bfloat16", False),
    "cmda_window_decode_33_slots": (33, 1, CMDA, 16, 9472, 1024, "bfloat16", "bfloat16", False, True),
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_paged_attention_kernel_compiles_for_the_v5e(one_chip, name):
    S, T, (H, K, D), bs, NB, MB, pool, qdt, quant, *bounded = SHAPES[name]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    scale = sds((NB, bs, K), "float32") if quant else None
    lower = sds((S, T), "int32") if bounded else None
    compiled = jax.jit(
        lambda q, k, v, t, p, n, ks, vs, lo: pk.paged_attention_kernel(
            q, k, v, t, p, block_size=bs, n_rep=H // K, n_tiles=n,
            k_scale=ks, v_scale=vs, lower=lo)).lower(
        sds((S, T, H, D), qdt), sds((NB, bs, K * D), pool),
        sds((NB, bs, K * D), pool), sds((S, MB), "int32"),
        sds((S, T), "int32"), sds((), "int32"), scale, scale,
        lower).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the trace, the benchmark's readers and chip_smoke.py find it by this
    assert "_paged_attention_call" in text


# id: (S, T, (H, KVH, D), num_blocks, max_blocks, pool dtype[, bounded]), at
# block_size 16: a launch's K/V write and its attention over one layer's pool
WRITE_THEN_ATTEND = {
    "yi_decode": (32, 1, YI, 4096, 128, "bfloat16"),
    "yi_chunk_64": (1, 64, YI, 4096, 128, "bfloat16"),
    "yi_float32_pool_decode": (32, 1, YI, 4096, 128, "float32"),
    "yi_int8_pool_decode": (32, 1, YI, 4096, 128, "int8"),
    "cmda_full_decode": (32, 1, CMDA, 12288, 1024, "bfloat16"),
    "cmda_window_decode": (32, 1, CMDA, 9472, 1024, "bfloat16", True),
    "cmda_window_chunk_512": (1, 512, CMDA, 9472, 1024, "bfloat16", True),
}


@pytest.mark.parametrize("name", sorted(WRITE_THEN_ATTEND))
def test_no_launch_copies_a_pool(one_chip, name):
    """The engine's K/V write, then the seam's kernel, with the pools
    donated: the scatter writes in place, the kernel reads what the scatter
    wrote, and nothing else in the program is as large as a pool."""
    S, T, (H, K, D), NB, MB, pool, *bounded = WRITE_THEN_ATTEND[name]
    bs, quant = 16, pool == "int8"
    qdt = "float32" if pool == "float32" else "bfloat16"
    eng = types.SimpleNamespace(
        block_size=bs, kv_quant="int8" if quant else None, _sc=sc,
        _kv=types.SimpleNamespace(max_blocks_per_slot=MB))

    def launch(kvl, q, k, v, tables, pos, n):
        kvl = PagedLlamaDecodeEngine._write_kv(
            eng, kvl, k, v, pos, tables, jnp.ones(pos.shape, bool))
        att = sc.paged_attention(
            q, kvl["k"], kvl["v"], tables, pos, block_size=bs,
            n_rep=H // K, n_tiles=n, k_scale=kvl.get("ksc"),
            v_scale=kvl.get("vsc"), use_kernel=True,
            lower=pos - 4095 if bounded else None)
        return att, kvl

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    kvl = {"k": sds((NB, bs, K * D), pool), "v": sds((NB, bs, K * D), pool)}
    if quant:
        kvl.update(ksc=sds((NB, bs, K), "float32"),
                   vsc=sds((NB, bs, K), "float32"))
    compiled = jax.jit(launch, donate_argnums=(0,)).lower(
        kvl, sds((S, T, H, D), qdt), sds((S, T, K, D), qdt),
        sds((S, T, K, D), qdt), sds((S, MB), "int32"), sds((S, T), "int32"),
        sds((), "int32")).compile()
    text = compiled.as_text()
    assert "_paged_attention_call" in text
    # every instruction whose result is pool-shaped, by its opcode: the
    # parameters, the scatters (inside their fusions) and the two fusions
    # that hold them; a reshape, copy, transpose or bitcast-convert is the
    # relayout this test is here to catch
    made = re.findall(
        r"= \(?\w+\[%d,%d,%d\]\S* ([\w-]+)\(" % (NB, bs, K * D), text)
    assert set(made) <= {"parameter", "scatter", "fusion"}, sorted(set(made))
    assert made.count("fusion") == 2, made
    mem = compiled.memory_analysis()
    # donated and written in place, at no more bytes than the values take
    # (an int8 block of 16 rows is two of its 8-row tiles: nothing is padded)
    assert mem.alias_size_in_bytes == sum(
        math.prod(a.shape) * a.dtype.itemsize for a in kvl.values())
    # and no temporary as large as one group of blocks, let alone a pool
    group = pk._GROUP_TOKENS * K * D * jnp.dtype(pool).itemsize
    assert mem.temp_size_in_bytes < group, (mem.temp_size_in_bytes, group)


# rows of the sorted buffer, row tile, columns: Command A+'s held experts
# ([16, 4096, 8192] gate and up, [16, 4096, 4096] down) at a decode launch of
# 32 rows (tile 16), a 512-row chunk (tile 64), the smallest chunk (8 rows)
EXPERT_ROWS = {
    "decode_gate_up": (512, 16, 8192), "decode_down": (512, 16, 4096),
    "chunk_512_gate_up": (5120, 64, 8192), "chunk_512_down": (5120, 64, 4096),
    "chunk_8_gate_up": (320, 16, 8192), "widest_tile": (6144, 128, 8192),
}


@pytest.mark.parametrize("name", sorted(EXPERT_ROWS))
def test_expert_rows_matmul_compiles_for_the_v5e(one_chip, name):
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    m, block_t, n = EXPERT_ROWS[name]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    compiled = jax.jit(
        lambda lhs, rhs, ids, live: gm.expert_rows_matmul(
            lhs, rhs, ids, live, block_t, use_kernel=True)).lower(
        sds((m, 4096), "bfloat16"), sds((16, 4096, n), "bfloat16"),
        sds((m // block_t,), "int32"), sds((), "int32")).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the trace and the benchmark's readers find it by this
    assert "_expert_rows_matmul_call" in text


# GLM-5.2's served shapes (64 heads over one latent row of 640 = 576 padded,
# 32 index heads of 128, blocks of 64, 7968 blocks, 776 a slot): a 32-slot
# decode step, a 512-row chunk (the latent kernel takes it 16 rows at a time),
# the smallest chunk
DSA = {"decode_32_slots": (32, 1), "chunk_512": (1, 512), "chunk_8": (1, 8)}


@pytest.mark.parametrize("name", sorted(DSA))
def test_the_sparse_latent_kernels_compile_for_the_v5e(one_chip, name):
    from paddle_tpu.ops.pallas import sparse_latent as sl
    S, T = DSA[name]
    bs, NB, MB, N = 64, 7968, 776, 51200

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    compiled = jax.jit(lambda q, w, pool, tables, pos: sl.index_scores(
        q, w, pool, tables, pos, block_size=bs, use_kernel=True)).lower(
        sds((S, T, 32, 128), "bfloat16"), sds((S, T, 32), "float32"),
        sds((NB, bs, 128), "bfloat16"), sds((S, MB), "int32"),
        sds((S, T), "int32")).compile()
    # the trace and the benchmark's readers find the kernels by these names
    assert "tpu_custom_call" in compiled.as_text()
    assert "_index_score_call" in compiled.as_text()
    compiled = jax.jit(lambda q, pool, tables, mask, pos: sl.latent_attention(
        q, pool, tables, mask, pos, block_size=bs, rank=512, scale=1 / 16,
        use_kernel=True)).lower(
        sds((S, T, 64, 640), "bfloat16"), sds((NB, bs, 640), "bfloat16"),
        sds((S, MB), "int32"), sds((S, T, N), "bool"),
        sds((S, T), "int32")).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "_latent_attention_call" in compiled.as_text()


def test_a_decode_step_scores_its_index_keys_where_they_lie(one_chip):
    """The index scores of a 32-slot decode step read the keys through the
    block table: no array of every slot's keys in position order (until PR 36
    XLA gathered them, `bf16[25600,64,128]`, 419 MB a layer) and a few MB of
    temporaries."""
    bs, NB, MB, S = 64, 7968, 776, 32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    compiled = jax.jit(lambda q, w, pool, tables, pos: sc.paged_index_scores(
        q, w, pool, tables, pos, block_size=bs, use_kernel=True)).lower(
        sds((S, 1, 32, 128), "bfloat16"), sds((S, 1, 32), "float32"),
        sds((NB, bs, 128), "bfloat16"), sds((S, MB), "int32"),
        sds((S, 1), "int32")).compile()
    text = compiled.as_text()
    assert "_index_score_call" in text
    for shape in ("[25600,64,128]", "[32,51200,128]"):
        assert shape not in text, shape
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("width", [640, 128])
def test_no_launch_copies_a_latent_or_an_index_pool(one_chip, width):
    """A row a token written into a donated pool of whole 128-lane rows lands
    in place, in the layout the pool came in. (A latent row of 576 does not:
    compiled for the v5e a `[NB, bs, 576]` pool comes out `{0,2,1}` and is
    copied whole into and out of every launch, which is why the model pads
    its row to 640.)"""
    NB, bs, MB = 7968, 64, 776

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    def launch(pools, rows, tables, pos):
        return sc.write_rows(pools, {"p": rows}, pos, tables,
                             jnp.ones(pos.shape, bool), bs)

    compiled = jax.jit(launch, donate_argnums=(0,)).lower(
        {"p": sds((NB, bs, width), "bfloat16")}, sds((32, 1, width), "bfloat16"),
        sds((32, MB), "int32"), sds((32, 1), "int32")).compile()
    made = re.findall(r"= \(?\w+\[%d,%d,%d\]\S* ([\w-]+)\(" % (NB, bs, width),
                      compiled.as_text())
    assert set(made) <= {"parameter", "scatter", "fusion"}, sorted(set(made))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == NB * bs * width * 2
    assert mem.temp_size_in_bytes < 1 << 20


# Solar Open 2's served shapes: 64 slots of 64 heads of a float32 [128, 128]
# state (the pool 1.07 GB a layer), a 512-row chunk and the smallest chunk
KDA = {"step_64_slots": None, "chunk_512": 512, "chunk_8": 8}


@pytest.mark.parametrize("name", sorted(KDA))
def test_the_kda_kernels_compile_for_the_v5e(one_chip, name):
    from paddle_tpu.ops.pallas import kda
    NS, H, d, T = 64, 64, 128, KDA[name]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    rows = lambda n: [sds((n, H, d), "float32")] * 4 + [sds((n, H), "float32")]
    if T is None:
        compiled = jax.jit(
            lambda S, q, k, v, g, b, act: kda.kda_step(
                S, q, k, v, g, b, act, use_kernel=True),
            donate_argnums=(0,)).lower(
            sds((NS, H, d, d), "float32"), *rows(NS), sds((NS,), "bool")).compile()
    else:
        compiled = jax.jit(
            lambda S, slot, fresh, q, k, v, g, b: kda.kda_chunk(
                S, slot, fresh, q, k, v, g, b, use_kernel=True),
            donate_argnums=(0,)).lower(
            sds((NS, H, d, d), "float32"), sds((), "int32"), sds((), "bool"),
            *rows(T)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the trace and the benchmark's readers find the kernels by these names
    assert ("_kda_step_call" if T is None else "_kda_chunk_call") in text
    # the pool is the kernel's input and its output: updated in place
    assert compiled.memory_analysis().alias_size_in_bytes == NS * H * d * d * 4


def test_no_decode_launch_copies_a_state_pool_or_a_kv_pool(one_chip, monkeypatch):
    """A GQA layer and a KDA layer of Solar Open 2 at the served widths, one
    decode launch over 64 slots with every pool donated: the K and V rows are
    scattered in place, the step kernel reads and writes the state where it
    lies, the convolution's tail (a row a slot: a `[slots, 3, C]` pool came out
    `{2,0,1}` and was copied every launch) is selected in place, and nothing in
    the program is as large as a pool."""
    from paddle_tpu.models import solar_open2 as so
    # the seams choose by the backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = so.SolarOpen2Config(num_hidden_layers=2, gqa_layers=(0,),
                              vocab_size=1024, dtype="bfloat16")
    model = so.SolarOpen2Serve(cfg, (0, 20))
    NS, NB, bs, MB, C = 64, 20480, 16, 640, 3 * 64 * 128
    eng = types.SimpleNamespace(
        block_size=bs, kv_quant=None, _sc=sc, _pa_kernel=True,
        _kv=types.SimpleNamespace(max_blocks_per_slot=MB))
    eng._write_kv = lambda *a: PagedLlamaDecodeEngine._write_kv(eng, *a)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    lps = [{k: sds(s, "bfloat16") for k, s in so.layer_shapes(cfg, i, 20).items()}
           for i in range(2)]
    spec = model.cache_spec(2)
    assert spec[1]["state"] == {"S": ((64, 128, 128), "float32"),
                                "conv": ((3 * C,), "bfloat16")}
    kvs = [{"k": sds((NB, bs, 1024), "bfloat16"), "v": sds((NB, bs, 1024), "bfloat16")},
           {n: sds((NS,) + shape, dt) for n, (shape, dt) in spec[1]["state"].items()}]

    def launch(kvs, lps, h, pos, tables, act):
        out = []
        for li in range(2):
            h, kvl, _, _ = model.layer(
                eng, li, lps[li], h, kvs[li], pos[:, None], tables,
                jnp.max(pos) // bs + 1, act[:, None], None, slots=None)
            out.append(kvl)
        return h, out

    compiled = jax.jit(launch, donate_argnums=(0,)).lower(
        kvs, lps, sds((NS, 1, 4096), "bfloat16"), sds((NS,), "int32"),
        sds((NS, MB), "int32"), sds((NS,), "bool")).compile()
    text = compiled.as_text()
    for kernel in ("_kda_step_call", "_paged_attention_call",
                   "_expert_rows_matmul_call"):
        assert kernel in text

    def made(shape):
        return set(re.findall(r"= \(?\w+\[%s\]\S* ([\w-]+)\(" % shape, text))
    assert made("%d,64,128,128" % NS) <= {"parameter", "get-tuple-element",
                                          "custom-call"}
    assert made("%d,%d,1024" % (NB, bs)) <= {"parameter", "scatter", "fusion"}
    assert not made("%d,%d" % (NS, 3 * C)) & {"copy", "transpose", "reshape"}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == (2 * NB * bs * 1024 * 2
                                       + NS * 64 * 128 * 128 * 4 + NS * 3 * C * 2)
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes


@pytest.mark.parametrize("program", ["decode", "chunk_512", "chunk_8"])
def test_the_engine_s_programs_keep_a_float32_state_in_place(
        one_chip, monkeypatch, program):
    """The ENGINE's own decode and chunk programs (`_decode_impl`,
    `_prefill_impl` through `_forward_paged` and `SolarOpen2Serve.layer`) at the
    served widths, a GQA layer and two KDA layers, every pool donated: each KDA
    layer's state goes through exactly one `_kda_step_call` (decode) or
    `_kda_chunk_call` (chunk), a float32 `[64, 64, 128, 128]` operand aliased to
    the call's output, and the program holds NO other array of a state's shape
    in any dtype: a state rounded below float32, or kept beside the pool,
    anywhere in the launch would be one. The benchmark's `recurrence_gap` drives
    these two calls at these shapes on the chip
    (`benchmark/runners/serve_paged_kda.py` `recurrence_probe`); this holds the
    served programs to them."""
    from paddle_tpu.models import solar_open2 as so
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = so.SolarOpen2Config(num_hidden_layers=3, gqa_layers=(0,),
                              vocab_size=1024, dtype="bfloat16")
    NS, NB, bs, MB, V = 64, 2048, 16, 640, 1024
    eng = object.__new__(PagedLlamaDecodeEngine)
    eng._m = so.SolarOpen2Serve(cfg, (0, 20))
    eng.cache_spec = eng._m.cache_spec(3)
    for sp in eng.cache_spec:
        sp.setdefault("pools", {})
    eng._stateful, eng.block_size, eng.kv_quant, eng._sc = True, bs, None, sc
    eng._pa_kernel, eng.dtype, eng.n_layers = True, jnp.bfloat16, 3
    eng._kv = types.SimpleNamespace(max_blocks_per_slot=MB)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    params = {"emb": sds((V, 4096), "bfloat16"), "norm": sds((4096,), "bfloat16"),
              "head": sds((V, 4096), "bfloat16"),
              "layers": [{k: sds(s, "bfloat16")
                          for k, s in so.layer_shapes(cfg, i, 20).items()}
                         for i in range(3)]}
    state = eng.cache_spec[1]["state"]
    kvs = {"k": [sds((NB, bs, 1024), "bfloat16"), None, None],
           "v": [sds((NB, bs, 1024), "bfloat16"), None, None],
           **{n: [None] + [sds((NS,) + shape, dt)] * 2
              for n, (shape, dt) in state.items()}}
    if program == "decode":
        compiled = jax.jit(eng._decode_impl, donate_argnums=(1,)).lower(
            params, kvs, sds((NS, 1), "int32"), sds((NS,), "int32"),
            sds((NS, MB), "int32"), sds((NS,), "bool")).compile()
        call = "_kda_step_call"
    else:
        B = int(program.split("_")[1])
        scalar = sds((), "int32")
        compiled = jax.jit(eng._prefill_impl, donate_argnums=(1,)).lower(
            params, kvs, sds((1, B), "int32"), sds((MB,), "int32"), scalar,
            scalar, scalar, scalar).compile()
        call = "_kda_chunk_call"
    text = compiled.as_text()
    pool = r"\[%d,64,128,128\]" % NS
    # every array of a state's shape, by dtype and by the operation that made it
    made = re.findall(r"= \(?(\w+)%s\S* ([\w-]+)\(" % pool, text)
    assert {dt for dt, _ in made} == {"f32"}, sorted(set(made))
    assert {op for _, op in made} <= {"parameter", "get-tuple-element",
                                      "custom-call"}, sorted(set(made))
    pools = set(re.findall(r"%%(\S+) = f32%s\S* parameter\(" % pool, text))
    assert len(pools) == 2                      # one a KDA layer
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and call in line]
    fed = set()
    for line in calls:
        # the call's first output is the operand it names, and that operand is
        # the donated pool itself: nothing stands between them
        operands = line.split("custom-call(")[1].split(")")[0]
        operands = re.sub(r"/\*.*?\*/", "", operands).split(", ")
        (at,) = re.findall(r"output_to_operand_aliasing=\{\{0\}: \((\d+), \{\}\)",
                           line)
        fed.add(operands[int(at)].lstrip("%"))
    assert len(calls) == 2 and fed == pools, (fed, pools)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * NS * 64 * 128 * 128 * 4


# power retention (Brumby): a 32-slot step over the served state (8 KV heads of
# 8,256 x 128 float32 a slot, the pool 1.08 GB a layer), a 512-row chunk and the
# smallest chunk
RETENTION = {"step_32_slots": None, "chunk_512": 512, "chunk_8": 8}


@pytest.mark.parametrize("name", sorted(RETENTION))
def test_the_retention_kernels_compile_for_the_v5e(one_chip, name):
    from paddle_tpu.ops.pallas import power_retention as pr
    NS, Hk, r, d, T = 32, 8, 5, 128, RETENTION[name]
    D = pr.feature_dim(d)

    def sds(shape, dtype="float32"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    pools = (sds((NS, Hk, D, d)), sds((NS, Hk, D)))
    if T is None:
        compiled = jax.jit(
            lambda S, z, q, k, v, g, act: pr.retention_step(
                S, z, q, k, v, g, act, use_kernel=True),
            donate_argnums=(0, 1)).lower(
            *pools, sds((NS, Hk * r, d)), sds((NS, Hk, d)), sds((NS, Hk, d)),
            sds((NS, Hk)), sds((NS,), "bool")).compile()
    else:
        compiled = jax.jit(
            lambda S, z, slot, fresh, q, k, v, g: pr.retention_chunk(
                S, z, slot, fresh, q, k, v, g, use_kernel=True),
            donate_argnums=(0, 1)).lower(
            *pools, sds((), "int32"), sds((), "bool"), sds((T, Hk * r, d)),
            sds((T, Hk, d)), sds((T, Hk, d)), sds((T, Hk))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the trace and the benchmark's readers find the kernels by these names
    assert ("_retention_step_call" if T is None
            else "_retention_chunk_call") in text
    # both pools are the kernel's inputs and its outputs: updated in place
    # (z's rows of 8,256 lanes are padded to whole 128-lane tiles)
    assert compiled.memory_analysis().alias_size_in_bytes \
        == NS * Hk * (D * d + 8320) * 4


@pytest.mark.parametrize("program", ["decode", "chunk_512"])
def test_the_retention_engine_keeps_its_float32_state_in_place(
        one_chip, monkeypatch, program):
    """The ENGINE's own decode and chunk programs for Brumby (`_decode_impl`,
    `_prefill_impl` through `BrumbyServe.layer`) at the served widths, two
    layers, 32 slots, the pools donated and no block table: each layer's `S`
    and `z` go through exactly one `_retention_step_call` (decode) or
    `_retention_chunk_call` (chunk) that takes the donated float32 pool and
    hands it back aliased, and the program holds no other array of the
    expanded width (8,256) in any dtype: phi of the rows formed in HBM, or a
    state rounded or copied on the way, would be one. The benchmark's
    `recurrence_gap` drives these two calls at these shapes on the chip
    (`benchmark/runners/serve_paged_state.py` `recurrence_probe`)."""
    from paddle_tpu.models import brumby as bm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L, NS = 2, 32
    cfg = bm.BrumbyConfig(num_hidden_layers=L, vocab_size=1024,
                          dtype="bfloat16")
    eng = object.__new__(PagedLlamaDecodeEngine)
    eng._m = bm.BrumbyServe(cfg)
    eng.cache_spec = eng._m.cache_spec(L)
    eng._stateful, eng.block_size, eng.kv_quant, eng._sc = True, 16, None, sc
    eng._pa_kernel, eng.dtype, eng.n_layers = None, jnp.bfloat16, L

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    H = cfg.hidden_size
    params = {"emb": sds((1024, H), "bfloat16"), "norm": sds((H,), "bfloat16"),
              "head": sds((1024, H), "bfloat16"),
              "layers": [{k: sds(s, "bfloat16")
                          for k, s in bm.layer_shapes(cfg).items()}
                         for _ in range(L)]}
    kvs = {n: [sds((NS,) + shape, dt)] * L
           for n, (shape, dt) in eng.cache_spec[0]["state"].items()}
    if program == "decode":
        compiled = jax.jit(eng._decode_impl, donate_argnums=(1,)).lower(
            params, kvs, sds((NS, 1), "int32"), sds((NS,), "int32"), None,
            sds((NS,), "bool")).compile()
        call = "_retention_step_call"
    else:
        scalar = sds((), "int32")
        compiled = jax.jit(eng._prefill_impl, donate_argnums=(1,)).lower(
            params, kvs, sds((1, 512), "int32"), None, scalar, scalar, scalar,
            scalar).compile()
        call = "_retention_chunk_call"
    text = compiled.as_text()
    pool = {r"%d,8,8256,128" % NS, r"%d,8,8256" % NS}
    # every array of the expanded width, by dtype, shape and maker
    wide = re.findall(r"= \(?(\w+)\[([\d,]*8256[\d,]*)\]\S* ([\w-]+)\(", text)
    assert {(dt, shape) for dt, shape, _ in wide} \
        == {("f32", shape) for shape in pool}, sorted(set(wide))
    # made by nothing but the pools' own passing; XLA may hold the small z
    # pool (8.5 MB) in VMEM across the call, a copy in and out of it
    assert {(shape, op) for _, shape, op in wide} <= {
        (shape, op) for shape in pool
        for op in ("parameter", "get-tuple-element", "custom-call")} | {
        ("%d,8,8256" % NS, "copy-done")}, sorted(set(wide))
    params_in = set(re.findall(r"%%(\S+) = f32\[(?:%s)\]\S* parameter\("
                               % "|".join(pool), text))
    assert len(params_in) == 2 * L              # S and z of each layer
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and call in line]
    # an operand that XLA moved to VMEM is the pool it was copied from
    moved = dict(re.findall(r"%(\S+) = \S+ copy-done\(%(\S+)\)", text))
    moved = {done: src for done, start in moved.items() for src in re.findall(
        r"%%%s = .* copy-start\(%%(\S+)\)" % re.escape(start), text)}
    fed = set()
    for line in calls:
        # each of the call's first two outputs is the operand it names, and
        # that operand is the donated pool itself: nothing stands between
        operands = line.split("custom-call(")[1].split(")")[0]
        operands = re.sub(r"/\*.*?\*/", "", operands).split(", ")
        for at in re.findall(r"\{(\d)\}: \((\d+), \{\}\)", line):
            name = operands[int(at[1])].lstrip("%")
            fed.add(moved.get(name, name))
    assert len(calls) == L and fed == params_in, (fed, params_in)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= L * NS * 8 * (8256 * 128 + 8256) * 4
