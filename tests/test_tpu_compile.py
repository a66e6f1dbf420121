"""The main path's kernels and steps, asked of the TPU's own compiler at
the 737M GPT's widths (the paged steps at the benchmark's two
configurations' too) — for a v5e that is described, not attached
(on-chip-measurement guide, section 2.3).  Nothing runs; what the chip's
compiler would refuse (a tiling it cannot lower, too much VMEM, a
program that does not fit HBM) is refused here, at no chip time.

gpt.py / llama.py pick flash attention by jax.default_backend(), which
is "cpu" in such a compile, so the kernel and the engine's jitted steps
are compiled directly.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu.models import decode, gpt, llama  # noqa: E402
from ray_tpu.ops import flash_attention as fa  # noqa: E402
from ray_tpu.serve.llm import engine  # noqa: E402

CFG = gpt.GPTConfig(vocab_size=32000, d_model=2048, n_heads=16, n_layers=12,
                    d_ff=8192, max_seq=1024, dtype=jnp.bfloat16, remat=False)
# The benchmark's two configurations (benchmarks/configs/).
MISTRAL_D16 = llama.LlamaConfig(
    vocab_size=32768, d_model=4096, n_heads=32, n_kv_heads=8, n_layers=16,
    d_ff=14336, max_seq=4096, rope_theta=1e6, dtype=jnp.bfloat16,
    remat=False, use_flash=False)
INTERNLM2 = llama.LlamaConfig(
    vocab_size=92544, d_model=2048, n_heads=16, n_kv_heads=8, n_layers=24,
    d_ff=8192, max_seq=4096, rope_theta=1e6, dtype=jnp.bfloat16,
    remat=False, use_flash=False)


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e 2x2, with the persistent compile
    cache off: an entry written by such a compile cannot be read back
    without a chip, and the next compile would warn about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it knows no such chip
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        tree)


@pytest.mark.parametrize("shape", [(4, 16, 2048, 128), (8, 16, 4096, 128),
                                   (4, 16, 8192, 128)])
def test_flash_kernels_compile(chip, shape):
    """fwd, dq and dkv Pallas kernels at the 737M head shape."""
    qkv = _on(chip, [jax.ShapeDtypeStruct(shape, jnp.bfloat16)] * 3)
    grad = jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v).astype(jnp.float32)
        .sum(), argnums=(0, 1, 2)))
    text = grad.lower(*qkv).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def _pool_results(text, pool_shape):
    """Of an optimised HLO text: the instructions whose result is one
    layer's pool, and the copies / update-slices whose result is the
    whole pool."""
    layer = "bf16[%s]" % ",".join(map(str, pool_shape[1:]))
    whole = "bf16[%s]" % ",".join(map(str, pool_shape))
    results = [ln.split(" = ", 1)[1] for ln in text.splitlines()
               if " = " in ln]
    per_layer = [r for r in results if r.startswith(layer)]
    moved = [r for r in results if r.startswith(whole)
             and re.search(r"\b(copy|dynamic-update-slice)\(", r)]
    return per_layer, moved


def _no_row_a_routed_pair(text, tokens, cfg, hidden):
    """Of a step's optimised HLO: no float32 array has a row a routed
    pair (`f32[tokens x top_k, hidden]`: 4096 x 4096 x 4 B = 64 MiB an
    expert layer of mimo's chunk, where the parent masked, gathered and
    summed three of them): the expert layer's arrays follow the slab it
    walks, not what the call routed."""
    pairs = "f32[%d,%d]" % (tokens * cfg.top_k, hidden)
    held = [ln for ln in text.splitlines() if " = " + pairs in ln]
    assert not held, held[:4]


def _ragged_kernels(text):
    return [ln for ln in text.splitlines() if "custom-call(" in ln
            and "tpu_custom_call" in ln and " %paged_attention" in ln]


def _ragged_tick_holds(text, cfg, cache, rows, blocks, page_size):
    """What a compiled tick of `models/exaone_moe.py` holds to on a TPU:
    one `ops/paged_attention.py` kernel a paged layer beside the expert
    layers' three grouped matmuls each; the pool reaches the kernel as
    it lies (K-EXAONE's [page, 8, 128] merged to [page x 8, 128] by a
    bitcast, never by a copy); and no array is as wide as the table's
    keys, but for the table itself, which the kernel is handed flat
    ([rows x blocks] page ids in scalar memory)."""
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(_ragged_kernels(text)) == cfg.n_global
    assert len(kernels) == 3 * cfg.n_moe + cfg.n_global, len(kernels)
    for pool in (cache["k"], cache["v"]):
        if pool.ndim != 5:
            continue                  # heads side by side: handed as it is
        L, P, psz, G, width = pool.shape
        merged = " = bf16[%d,%d,%d,%d]" % (L, P, psz * G, width)
        made = [ln for ln in text.splitlines() if merged in ln]
        assert made and all(" bitcast(" in ln for ln in made), made[:4]
    shapes = [(kind, dims.split(",")) for kind, dims in
              re.findall(r" = (\w+)\[([\d,]+)\]", text)]
    wide = [s for s in shapes if str(blocks * page_size) in s[1]
            and s != ("s32", [str(rows * blocks)])]
    assert not wide, wide[:4]


# model, engine rows, blocks a row, pages (the engine's kv_pages + the
# trash page)
STEP_MODELS = {"gpt737m": (CFG, gpt, 8, 1024 // 16, 8 * 64 + 1),
               "mistral-d16": (MISTRAL_D16, llama, 16, 4096 // 16, 3585),
               "internlm2": (INTERNLM2, llama, 16, 4096 // 16, 2049)}


def _compile_step(chip, step, cfg, mod, rows, blocks, pages):
    """The engine's own jitted decode tick ([rows, 1]) or prefill chunk
    ([1, 32], or [1, 256]: the width an engine takes when it is given
    none) of a dense model, compiled for the described chip from
    shapes: (the compiled program, the pool's K as a shape).  Compiled
    as on a TPU (`as_on_a_tpu`), a tick reads each row's own pages
    through `ops/paged_attention.py`."""
    params = _on(chip, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda x: x.astype(cfg.dtype),
            mod.init_params(cfg, jax.random.PRNGKey(0)))))
    cache = _on(chip, jax.eval_shape(
        lambda: decode.init_paged_cache(cfg, pages, 16)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
    else:
        width = engine._RIDGE_CHUNK if step == "prefill_chunk_default" \
            else 32
        lowered = engine._prefill_chunk.lower(
            params, i32(1, width), i32(), cache, i32(1, blocks), cfg)
    return lowered.compile(), cache["k"]


STEPS = ["decode_tick", "prefill_chunk", "prefill_chunk_default"]


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """`decode._on_tpu` says yes while a dense step is traced (such a
    compile's default backend is the CPU), and no program traced under
    the other answer is found again."""
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    engine._paged_tick.clear_cache()
    yield
    engine._paged_tick.clear_cache()


def _dense_tick_holds(text, pool_shape, rows):
    """What a compiled dense tick holds to on a TPU: one
    `ops/paged_attention.py` kernel in the layer scan's body, handed K
    and V as they lie (the pool's [page, heads, 128] merged to [page x
    heads, 128] by a bitcast, never by a copy), and no float32 array a
    span wide for every row ([rows, span columns, heads, 128]: what the
    span loop cast its gathered keys to)."""
    assert len(_ragged_kernels(text)) == 1
    L, P, psz, G, Dh = pool_shape
    merged = " = bf16[%d,%d,%d,%d]" % (L, P, psz * G, Dh)
    made = [ln for ln in text.splitlines() if merged in ln]
    assert len(made) == 2 and all(" bitcast(" in ln for ln in made), made[:4]
    span = decode.paged_span_blocks(rows * psz * G * Dh * 2, psz, 1 << 20)
    cast = " = f32[%d,%d,%d,%d]" % (rows, span * psz, G, Dh)
    assert not [ln for ln in text.splitlines() if cast in ln]


@pytest.mark.parametrize("step", STEPS)
@pytest.mark.parametrize("model", list(STEP_MODELS))
def test_paged_step_compiles(chip, model, step, as_on_a_tpu):
    """The engine's own jitted decode tick ([rows, 1]) and prefill chunk
    ([1, 32] and the default [1, 256]): the 737M GPT over 8 rows x 1024
    tokens, and the benchmark's two configurations at their real widths
    and pools.  The pool is gigabytes there, and a step may hold no
    second one: no temporary the size of a layer's pool, no copy of the
    whole.  The tick's attention is the ragged kernel (PR 60), and it
    holds less than the span loop's did; a chunk calls no kernel."""
    cfg, mod, rows, blocks, pages = STEP_MODELS[model]
    compiled, pool = _compile_step(chip, step, cfg, mod, rows, blocks, pages)
    mem = compiled.memory_analysis()
    # with the pool as the layer scan's xs/ys: 4.19 / 3.94 GiB (Mistral
    # tick / chunk), 3.38 / 3.13 (InternLM2); as its carry 0.126 / 0.0003;
    # attention span by span (PR 29) 0.0006 / 0.0005; a 256-token chunk
    # (PR 33) 0.0007 / 0.0005; the tick through the kernel (PR 60)
    # 0.00036 (0.369 MiB against the span loop's 0.616, both models)
    text = compiled.as_text()
    if step == "decode_tick":
        assert mem.temp_size_in_bytes < 1 << 19, mem.temp_size_in_bytes
        _dense_tick_holds(text, pool.shape, rows)
    else:
        assert mem.temp_size_in_bytes < 1 << 29, \
            mem.temp_size_in_bytes / 2**30
        assert not _ragged_kernels(text)
    per_layer, moved = _pool_results(text, pool.shape)
    assert not per_layer, per_layer[:4]
    assert not moved, moved[:4]


@pytest.mark.parametrize("model", ["mistral-d16", "internlm2"])
def test_the_dense_tick_where_there_is_no_tpu_walks_spans(chip, model):
    """The same tick traced as on any other backend: no kernel, the
    span loop's temporaries (0.616 MiB), and still no second pool."""
    cfg, mod, rows, blocks, pages = STEP_MODELS[model]
    engine._paged_tick.clear_cache()
    compiled, pool = _compile_step(chip, "decode_tick", cfg, mod, rows,
                                   blocks, pages)
    engine._paged_tick.clear_cache()
    text = compiled.as_text()
    assert not _ragged_kernels(text)
    mem = compiled.memory_analysis()
    assert 1 << 19 < mem.temp_size_in_bytes < 1 << 20
    per_layer, moved = _pool_results(text, pool.shape)
    assert not per_layer and not moved, (per_layer[:4], moved[:4])


@pytest.mark.parametrize("model", ["mistral-d16", "internlm2"])
def test_tier_read_compiles_at_one_shape_and_moves_no_pool(chip, model):
    """The gather a demotion dispatches (decode.paged_read_pages), at the
    benchmark's dense pools and the one count of ids the engine ever
    asks for: its result is the stack of pages (32 MiB or less), it
    holds no temporary to speak of beside it, and no instruction's
    result is a layer's pool or the whole of it."""
    cfg, _, _, _, pages = STEP_MODELS[model]
    cache = _on(chip, jax.eval_shape(
        lambda: decode.init_paged_cache(cfg, pages, 16)))
    size = decode.paged_read_batch(cache)
    assert size == {"mistral-d16": 32, "internlm2": 21}[model]
    ids = jax.ShapeDtypeStruct((size,), jnp.int32, sharding=chip)
    compiled = decode.paged_read_pages.lower(cache, ids).compile()
    mem = compiled.memory_analysis()
    page = 2 * cfg.n_layers * 16 * cfg.n_kv_heads * cfg.head_dim * 2
    assert size * page <= mem.output_size_in_bytes <= 2 * size * page
    assert mem.temp_size_in_bytes < 1 << 26, mem.temp_size_in_bytes / 2**20
    per_layer, moved = _pool_results(compiled.as_text(), cache["k"].shape)
    assert not per_layer, per_layer[:4]
    assert not moved, moved[:4]


@pytest.mark.parametrize("step", STEPS)
def test_paged_step_compiles_at_a_32k_width(chip, step, as_on_a_tpu):
    """InternLM2's tick and chunk over a virtual width of 32,768 (2,048
    blocks a row, the benchmark's 2,048-page pool): attention walks
    each row's own pages (the tick) or spans of the table up to what
    the rows hold (a chunk), so nothing the program keeps is as wide as
    the table, but the table itself, which the tick's kernel is handed
    flat ([16 rows x 2,048 blocks] page ids in scalar memory).  The
    same temporaries as at 4,096 (0.0004 / 0.0005 GiB), and no other
    array with a dimension of the width;
    the body that gathered the virtual width compiled here with 1.03 GiB
    of temporaries in the tick and 0.063 in the chunk, eight times what
    it held at 4,096.  What `internlm2-longctx` needs (ROADMAP R0)."""
    import dataclasses
    wide = 32768
    compiled, pool = _compile_step(
        chip, step, dataclasses.replace(INTERNLM2, max_seq=wide), llama,
        16, wide // 16, 2049)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 26, mem.temp_size_in_bytes / 2**30
    text = compiled.as_text()
    per_layer, moved = _pool_results(text, pool.shape)
    assert not per_layer and not moved, (per_layer[:4], moved[:4])
    shapes = re.findall(r" = (\w+)\[([\d,]+)\]", text)
    assert not [s for s in shapes if str(wide) in s[1].split(",")
                and s != ("s32", str(16 * (wide // 16)))]  # (the table)


# The third configuration (benchmarks/configs/minicpm-sala-d16.json): a
# model with its own paged step and cache, built as the benchmark builds
# it, at the configuration's sizes.
@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_sala_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of minicpm-sala-d16: under 1 GiB of temporaries
    (11.4 GB of weights, pages and state are resident), the page pool
    never re-laid or copied, and in the tick no array as wide as a row's
    virtual sequence (`max_seq`): attention reads 128 page slots a row
    and the scorer one compressed key per 16 tokens.  The chunk with its
    sparse branch as the chip runs it (a Pallas kernel, not its
    interpreter: `minicpm_sala._on_tpu` is patched true): one
    `ops/paged_prefill_attention.py` kernel handed the pools as they
    lie, and no sort anywhere in the program (the selection is a mask;
    the tick's program keeps its `top_k`)."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import minicpm_sala
    monkeypatch.setattr(minicpm_sala, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs", "minicpm-sala-d16.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes / 2**30
    # weights 9.39 GiB + pages, compressed keys and state 1.28
    assert mem.argument_size_in_bytes < 11 * 2**30
    text = compiled.as_text()
    pool = "bf16[%s]" % ",".join(map(str, cache["k"].shape))
    layouts = set(re.findall(re.escape(pool) + r"\{([\d,]+)", text))
    assert layouts == {"4,3,2,1,0"}, layouts       # never re-laid
    moved = [ln for ln in text.splitlines()
             if re.search(r"= " + re.escape(pool) + r"\S* copy\(", ln)]
    assert not moved, moved[:4]
    sorts = [ln for ln in text.splitlines()
             if re.search(r" (sort|topk)\(|TopK", ln)]
    if step == "decode_tick":
        wide = blocks * e["page_size"]
        shapes = re.findall(r" = \w+\[([\d,]+)\]", text)
        assert not [s for s in shapes if str(wide) in s.split(",")]
        assert sorts                    # (what the chunk's check looks for)
    else:
        calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
                 and "query_block_attention" in ln]
        assert calls and all(ln.count(pool) == 2 for ln in calls), calls[:2]
        assert not sorts, sorts[:4]


# The fourth configuration (benchmarks/configs/deepseek-v2-ep4-d5.json):
# latent attention over a latent page and an expert layer that holds 40
# of 160 experts, built as the benchmark builds it, at its sizes.
@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_dsv2_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of deepseek-v2-ep4-d5 with the grouped matmul as
    the chip runs it (a Pallas kernel, not its interpreter: the model
    asks jax.default_backend(), which is "cpu" here): 10.3 GB of weights
    and a 2.5 GB latent pool are resident, a step holds under 0.5 GiB
    beside them, the pool is never re-laid or copied, and the tick
    forms no key or value of head width from the latents
    ([rows, keys, 128, 128 or 192]): it attends in the latent space,
    through one `ops/paged_attention.py` kernel a layer that is handed
    the latent pool alone, as it lies (values are its keys' first 512
    lanes: no second pool, no gathered span of the table's rows)."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import deepseek_v2
    monkeypatch.setattr(deepseek_v2, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs",
                           "deepseek-v2-ep4-d5.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # 0.016 GiB (tick) / 0.139 (a 512-token chunk)
    assert mem.temp_size_in_bytes < 1 << 29, mem.temp_size_in_bytes / 2**30
    # weights 9.62 GiB + the latent pool 2.34
    assert 11.5 * 2**30 < mem.argument_size_in_bytes < 12.5 * 2**30
    text = compiled.as_text()
    # three grouped matmuls an expert layer
    assert text.count("tpu_custom_call") >= 3 * cfg.n_moe
    pool = "bf16[%s]" % ",".join(map(str, cache["lat"].shape))
    layouts = set(re.findall(re.escape(pool) + r"\{([\d,]+)", text))
    assert layouts == {"3,2,1,0"}, layouts         # never re-laid
    moved = [ln for ln in text.splitlines()
             if re.search(r"= " + re.escape(pool) + r"\S* copy\(", ln)]
    assert not moved, moved[:4]
    _no_row_a_routed_pair(text, rows if step == "decode_tick"
                          else e["prefill_chunk"], cfg, c["hidden_size"])
    _nothing_of_the_selection(text, step)
    if step == "decode_tick":
        shapes = [s.split(",") for s in
                  re.findall(r" = \w+\[([\d,]+)\]", text)]
        expanded = [s for s in shapes if len(s) == 4 and s[0] == str(rows)
                    and s[-1] in ("128", "192") and "128" in s[1:3]]
        assert not expanded, expanded[:4]
        ragged = [ln for ln in text.splitlines() if "custom-call(" in ln
                  and "tpu_custom_call" in ln and " %paged_attention" in ln]
        assert len(ragged) == cfg.n_layers, len(ragged)
        assert text.count("tpu_custom_call") == 3 * cfg.n_moe + cfg.n_layers
        # each is handed the pool once, and no span of it is gathered
        # for the call's rows ([rows, keys of a span, 640])
        assert all(ln.count(pool) == 1 for ln in ragged), ragged[:1]
        width = str(cache["lat"].shape[-1])
        spans = [s for s in shapes if len(s) >= 3 and s[0] == str(rows)
                 and s[-1] == width and s[1:-1] != [str(cfg.n_heads)]]
        assert not spans, spans[:4]


def _nothing_of_the_selection(text, step):
    """A latent-attention program of a model that chooses no keys is the
    program it was before `deepseek_v2._attn_chunk` / `_attn_tick` took
    `chosen`: its attention stands under its own scope and no
    instruction under the selection's."""
    assert "dsa_" not in text
    assert ("mla_absorb_attend" if step == "decode_tick"
            else "mla_expand_attend") in text


# The fifth configuration (benchmarks/configs/k-exaone-ep8-d5.json):
# window layers that hold a ring of 128 tokens a decode row beside one
# global layer that pages, and an expert layer that holds 16 of 128
# experts, built as the benchmark builds it, at its sizes.
@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_kexaone_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of k-exaone-ep8-d5 with the grouped matmul as the
    chip runs it: 7.4 GB of weights, the ONE global layer's pool and
    0.27 GB of rings are resident, a step holds under 0.5 GiB beside
    them, neither the pool nor the rings are re-laid or copied, and no
    array of a window layer's attention is as wide as the table: the
    tick scores [rows, heads, 128 ring entries], never max_seq."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import deepseek_v2, exaone_moe
    monkeypatch.setattr(deepseek_v2, "_on_tpu", lambda: True)
    monkeypatch.setattr(exaone_moe, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs", "k-exaone-ep8-d5.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])
    assert cache["k"].shape[0] == 1 and cache["wk"].shape[:3] == (
        4, rows, 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # 0.042 GiB (tick) / 0.112 (a 512-token chunk)
    assert mem.temp_size_in_bytes < 1 << 29, mem.temp_size_in_bytes / 2**30
    # weights 6.91 GiB + the global layer's pool + rings 0.25
    pool = 2 * cache["k"].size * 2 / 2**30
    assert 7.1 + pool < mem.argument_size_in_bytes / 2**30 < 7.3 + pool
    text = compiled.as_text()
    # three grouped matmuls an expert layer
    assert text.count("tpu_custom_call") >= 3 * cfg.n_moe
    for name in ("k", "wk"):
        held = "bf16[%s]" % ",".join(map(str, cache[name].shape))
        layouts = set(re.findall(re.escape(held) + r"\{([\d,]+)", text))
        assert layouts == {"4,3,2,1,0"}, (name, layouts)   # never re-laid
        moved = [ln for ln in text.splitlines()
                 if re.search(r"= " + re.escape(held) + r"\S* copy\(", ln)]
        assert not moved, moved[:4]
    _no_row_a_routed_pair(text, rows if step == "decode_tick"
                          else e["prefill_chunk"], cfg, c["hidden_size"])
    if step == "decode_tick":
        # 13 MiB (the span loop's gathered spans made it 43)
        assert mem.temp_size_in_bytes < 1 << 25, mem.temp_size_in_bytes
        _ragged_tick_holds(text, cfg, cache, rows, blocks, e["page_size"])
        return
    # nothing is as wide as the table (14,336 columns, 224 blocks of
    # pages but for the block tables themselves)
    shapes = [s.split(",") for s in re.findall(r" = \w+\[([\d,]+)\]", text)]
    wide = [s for s in shapes if str(blocks * e["page_size"]) in s]
    assert not wide, wide[:4]


# The seventh configuration (benchmarks/configs/mimo-v2-flash-ep16-d7.json):
# two full layers that page 4 heads of 192-wide keys and 128-wide values
# beside five window layers whose rings hold 8, a sink in every window
# softmax, and an expert layer that holds 16 of 256 experts, built as the
# benchmark builds it, at its sizes (K-EXAONE's functions: what they
# learned must still compile for the chip at two widths).
@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_mimo_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of mimo-v2-flash-ep16-d7 with the grouped matmul as
    the chip runs it: 6.86 GB of weights, the TWO full layers' pool (k
    and v of different widths) and 0.21 GB of rings are resident, a step
    holds under 0.5 GiB beside them, none of the four cache arrays is
    re-laid or copied, and no array is as wide as the table."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import deepseek_v2, exaone_moe
    monkeypatch.setattr(deepseek_v2, "_on_tpu", lambda: True)
    monkeypatch.setattr(exaone_moe, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs",
                           "mimo-v2-flash-ep16-d7.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])
    # (a token's heads side by side: an array that ends in [4, 192] is
    # given a layout with the pages innermost and re-laid every step)
    assert cache["k"].shape[0] == 2 and cache["k"].shape[3:] == (4 * 192,)
    assert cache["v"].shape[3:] == (4 * 128,)
    assert cache["wk"].shape == (5, rows, 128, 8 * 192)
    assert cache["wv"].shape == (5, rows, 128, 8 * 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 29, mem.temp_size_in_bytes / 2**30
    # weights 6.40 GiB + the full layers' pool + rings 0.20
    held = sum(cache[k].size * 2 for k in ("k", "v", "wk", "wv")) / 2**30
    assert 6.35 + held < mem.argument_size_in_bytes / 2**30 < 6.5 + held
    text = compiled.as_text()
    # three grouped matmuls an expert layer
    assert text.count("tpu_custom_call") >= 3 * cfg.n_moe
    for name in ("k", "v", "wk", "wv"):
        held = "bf16[%s]" % ",".join(map(str, cache[name].shape))
        layouts = set(re.findall(re.escape(held) + r"\{([\d,]+)", text))
        assert layouts == {"3,2,1,0"}, (name, layouts)     # never re-laid
        moved = [ln for ln in text.splitlines()
                 if re.search(r"= " + re.escape(held) + r"\S* copy\(", ln)]
        assert not moved, moved[:4]
    _no_row_a_routed_pair(text, rows if step == "decode_tick"
                          else e["prefill_chunk"], cfg, c["hidden_size"])
    if step == "decode_tick":
        # 23 MiB (18 with the span loop: the queries laid into their
        # heads' lanes, [64, 64, 768], go through HBM once a full layer)
        assert mem.temp_size_in_bytes < 1 << 25, mem.temp_size_in_bytes
        _ragged_tick_holds(text, cfg, cache, rows, blocks, e["page_size"])
        return
    # nothing is as wide as the table (27,648 columns, 432 blocks of
    # pages but for the block tables themselves)
    shapes = [s.split(",") for s in re.findall(r" = \w+\[([\d,]+)\]", text)]
    wide = [s for s in shapes if str(blocks * e["page_size"]) in s]
    assert not wide, wide[:4]


# The sixth configuration (benchmarks/configs/jamba2-3b.json): 26 Mamba
# layers that hold a float32 scan state and a convolution tail a decode
# row beside two attention layers that page one key-value head, built as
# the benchmark builds it, at its sizes.
def test_ssm_kernels_compile(chip):
    """The chunk's selective scan and the tick's step as the chip runs
    them, at the mixer's widths (256 tokens or 128 rows, 5,120 channels,
    16 states): one Pallas call each; the scan holds nothing beside its
    arguments, the step updates 26 layers' states in place."""
    from ray_tpu.ops import ssm

    def on(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    f32 = jnp.float32
    compiled = jax.jit(ssm.scan_pallas).lower(
        on(f32, 256, 5120), on(f32, 256, 5120), on(f32, 256, 16),
        on(f32, 256, 16), on(f32, 16, 5120), on(f32, 16, 5120)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 24
    compiled = jax.jit(ssm.step_pallas, donate_argnums=5).lower(
        on(f32, 128, 5120), on(f32, 128, 5120), on(f32, 128, 16),
        on(f32, 128, 16), on(f32, 16, 5120), on(f32, 26, 128, 16, 5120),
        on(jnp.int32), on(jnp.bool_, 128)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 24
    assert mem.alias_size_in_bytes == 26 * 128 * 16 * 5120 * 4


@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_jamba_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of jamba2-3b with the scan as the chip runs it:
    6.06 GB of weights, the two attention layers' pool and 1.19 GB of
    state and tails are resident, a step holds under 0.25 GiB beside
    them (no [tokens, 16, 5120] float32 product, no copy of the state),
    and neither the pool nor the state is re-laid or copied."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.ops import ssm
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs", "jamba2-3b.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])
    assert cache["k"].shape == (2, 6145, 1, 64, 128)
    assert cache["ssm"].shape == (26, rows, 16, 5120)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # 0.002 GiB (tick) / 0.13 (a 256-token chunk)
    assert mem.temp_size_in_bytes < 1 << 28, mem.temp_size_in_bytes / 2**30
    # weights 5.65 GiB + pool 0.375 + state and tails 1.11
    assert 7.0 < mem.argument_size_in_bytes / 2**30 < 7.3
    text = compiled.as_text()
    # one kernel a run of Mamba layers: the chunk's scan, the tick's step
    assert text.count("tpu_custom_call") == 3
    for name, order in (("k", "4,3,2,1,0"), ("ssm", "3,2,1,0"),
                        ("conv", "2,1,0")):
        held = "%s[%s]" % ({"float32": "f32", "bfloat16": "bf16"}[
            cache[name].dtype.name], ",".join(map(str, cache[name].shape)))
        layouts = set(re.findall(re.escape(held) + r"\{([\d,]+)", text))
        assert layouts == {order}, (name, layouts)         # never re-laid
        moved = [ln for ln in text.splitlines()
                 if re.search(r"= " + re.escape(held) + r"\S* copy\(", ln)]
        assert not moved, moved[:4]
    # nothing is as wide as the table (3,072 columns) but the tables
    shapes = [s.split(",") for s in re.findall(r" = \w+\[([\d,]+)\]", text)]
    wide = [s for s in shapes if str(blocks * e["page_size"]) in s]
    assert not wide, wide[:4]


# The eighth configuration (benchmarks/configs/zaya1-8b-pp2-d20.json):
# twenty layers that EACH page 2 heads of 128 (a token's heads side by
# side, 1 KB a token and layer) and keep three tails a decode row, top-1
# of 16 experts through the shared grouped matmul, a tied head of
# 262,272 rows, built as the benchmark builds it, at its sizes.
@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_zaya_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of zaya1-8b-pp2-d20 with the grouped matmul and the
    ragged kernel as the chip runs them: 9.4 GB of weights, every
    layer's pool and 10 MB of tails are resident, neither the pool nor a
    tail array is re-laid or copied, a tick is one paged-attention
    kernel and three grouped matmuls a layer and holds no array as wide
    as the table, and what a step holds beside its arguments is its
    float32 logits ([rows or chunk, 262272]) and little else."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import deepseek_v2, exaone_moe
    monkeypatch.setattr(deepseek_v2, "_on_tpu", lambda: True)
    monkeypatch.setattr(exaone_moe, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs", "zaya1-8b-pp2-d20.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])
    L, pool = 20, (20, e["kv_pages"] + 1, e["page_size"], 2 * 128)
    assert cache["k"].shape == cache["v"].shape == pool
    assert cache["cz"].shape == cache["cc"].shape == (L, rows, 1280)
    assert cache["cv"].shape == (L, rows, 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
        logits = rows * cfg.vocab_size * 4
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
        logits = 0      # the chunk's are its result, not a temporary
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < logits + (1 << 28), \
        mem.temp_size_in_bytes / 2**30
    held = sum(cache[k].size * 2 for k in ("k", "v", "cz", "cc", "cv"))
    assert arch.weight_bytes(c) + held \
        < mem.argument_size_in_bytes < arch.weight_bytes(c) + held + (1 << 26)
    text = compiled.as_text()
    for name in ("k", "v", "cz", "cc", "cv"):
        held = "bf16[%s]" % ",".join(map(str, cache[name].shape))
        moved = [ln for ln in text.splitlines()
                 if re.search(r"= " + re.escape(held) + r"\S* copy\(", ln)]
        if name in "kv":
            layouts = set(re.findall(re.escape(held) + r"\{([\d,]+)", text))
            assert layouts == {"3,2,1,0"}, (name, layouts)  # never re-laid
            assert not moved, moved[:4]
        else:
            # a tail array (5 MB, or 0.5) may be taken into fast memory
            # in a layout of the compiler's own: once in, once out (two
            # of the three are of one shape)
            assert len(moved) <= 4, moved[:4]
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "tpu_custom_call" in ln]
    if step == "prefill_chunk":
        assert len(kernels) == 3 * L, len(kernels)
        return
    ragged = [ln for ln in kernels if " %paged_attention" in ln]
    assert len(ragged) == L and len(kernels) == 4 * L, len(kernels)
    shapes = [(kind, dims.split(",")) for kind, dims in
              re.findall(r" = (\w+)\[([\d,]+)\]", text)]
    wide = [s for s in shapes if str(blocks * e["page_size"]) in s[1]
            and s != ("s32", [str(rows * blocks)])]
    assert not wide, wide[:4]


# The ninth configuration (benchmarks/configs/sdar-30b-a3b-pp8-d6.json):
# generation by diffusion over blocks: the engine's third program.
@pytest.mark.parametrize("step", ["block_step", "prefill_chunk"])
def test_sdar_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of sdar-30b-a3b-pp8-d6 as the chip runs them: 8.7
    GB of weights and a 3.6 GB pool are resident and never re-laid or
    copied; a block step (`engine._paged_block_step`, 128 rows x 4
    columns) is one `ops/paged_attention.py` kernel a layer, handed the
    pool as it lies and 4 x 32 query heads a row, beside three grouped
    matmuls, holds no array as wide as the table, and what it holds
    beside its arguments is its float32 logits ([512, 151936], their
    softmax's reductions) and little else; a chunk's logits are its
    result."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import deepseek_v2, sdar_moe
    monkeypatch.setattr(deepseek_v2, "_on_tpu", lambda: True)
    monkeypatch.setattr(sdar_moe, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs",
                           "sdar-30b-a3b-pp8-d6.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    B = cfg.block_length
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])
    L, pool = 6, (6, e["kv_pages"] + 1, e["page_size"], 4 * 128)
    assert cache["k"].shape == cache["v"].shape == pool

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def i32(*shape):
        return arr(jnp.int32, *shape)

    if step == "block_step":
        lowered = engine._paged_block_step.lower(
            params, i32(rows, B), arr(bool, rows, B), i32(rows, B),
            arr(bool, rows, B), arr(bool, rows), i32(rows), cache,
            i32(rows, blocks), cfg, with_logits=False)
        logits = rows * B * cfg.vocab_size * 4
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
        logits = 0      # the chunk's are its result, not a temporary
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * logits + (1 << 29), \
        mem.temp_size_in_bytes / 2**30
    held = sum(cache[k].size * 2 for k in ("k", "v"))
    assert arch.weight_bytes(c) + held \
        < mem.argument_size_in_bytes < arch.weight_bytes(c) + held + (1 << 26)
    text = compiled.as_text()
    per_layer, moved = _pool_results(text, pool)
    assert not per_layer and not moved, (per_layer[:2], moved[:2])
    held = "bf16[%s]" % ",".join(map(str, pool))
    layouts = set(re.findall(re.escape(held) + r"\{([\d,]+)", text))
    assert layouts == {"3,2,1,0"}, layouts              # never re-laid
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "tpu_custom_call" in ln]
    if step == "prefill_chunk":
        assert len(kernels) == 3 * L, len(kernels)
        return
    ragged = [ln for ln in kernels if " %paged_attention" in ln]
    assert len(ragged) == L and len(kernels) == 4 * L, len(kernels)
    # a row's call holds B x 32 query heads, each in its own key-value
    # head's lanes of a row as wide as the four
    assert all("bf16[%d,%d,512]" % (rows, B * 32) in ln for ln in ragged)
    shapes = [(kind, dims.split(",")) for kind, dims in
              re.findall(r" = (\w+)\[([\d,]+)\]", text)]
    # (the table's 64 x 64 keys are as many as the step's routed pairs,
    # 512 x 8, which the expert layer's one slab holds a row each:
    # `[4096]` ids, `[4096, 2048]` and `[4096, 768]` rows are not the
    # table, whose width would stand behind a dimension of rows)
    assert rows * B * cfg.top_k == blocks * e["page_size"]
    wide = [s for s in shapes if str(blocks * e["page_size"]) in s[1][1:]]
    assert not wide, wide[:4]
    # the logits are reduced as the head gives them, [rows x B, V]:
    # an array that ends in [B, V] would pad B to the tile's 8 rows
    cubes = [s for s in shapes if s[1][-2:] == [str(B), str(cfg.vocab_size)]]
    assert not cubes, cubes[:4]


# The expert walk alone (deepseek_v2.routed_experts), at each of the five
# expert configurations' two calls: the tile `_tiles` picks is what the
# kernel's fast memory has to hold, so a width that outgrows it fails
# here and not on the chip.
# The tenth configuration (benchmarks/configs/ling-3.0-flash-ep8-d6.json):
# five Kimi-Delta-Attention layers whose 2 MiB-a-row float32 state is
# stepped in place, one latent-attention layer that pages, 64 held of
# 512 sigmoid-routed experts, built as the benchmark builds it, at its
# sizes (256 rows: 2.68 GB of state).
def test_kda_kernels_compile(chip):
    """The tick's step as the chip runs it, at the mixer's widths (256
    rows, 32 heads of 128 x 128): one Pallas call that holds nothing
    beside its arguments and updates 5 layers' states in place; and the
    chunk at both chunk widths: the kernel (one Pallas call told `valid`,
    the streams read as they lie: temporaries under 16 MiB, and the fast
    memory it asks for is enough, or the compile raises) beside the
    plain-XLA form the other backends run (allowed 512 MiB)."""
    from ray_tpu.ops import kda

    def on(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    f32 = jnp.float32
    B, H, d = 256, 32, 128
    vec = on(f32, B, H, d)
    compiled = jax.jit(kda.step_pallas, donate_argnums=5).lower(
        vec, vec, vec, vec, on(f32, B, H), on(f32, 5, B, H, d, d),
        on(jnp.int32), on(jnp.bool_, B)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 24
    assert mem.alias_size_in_bytes == 5 * B * H * d * d * 4
    for T in (512, 1024):
        tok = on(f32, T, H, d)
        args = (tok, tok, tok, tok, on(f32, T, H), on(f32, H, d, d))
        compiled = jax.jit(kda.chunk_pallas).lower(
            *args, on(jnp.int32)).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        assert " %kda_chunk" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 24
        compiled = jax.jit(kda.chunk_xla).lower(*args).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 29


@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_ling3_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of ling-3.0-flash-ep8-d6 with the step kernel, the
    grouped matmul and the ragged kernel as the chip runs them: 4.85 GB
    of weights, one layer's latent pool and 2.78 GB of state and tails
    are resident; the delta-rule state is float32, donated through the
    step and updated IN PLACE (one layout, no copy of [5, 256, 32, 128,
    128]); the latent pool is never re-laid or copied; the tails keep one
    layout; a tick is one step kernel a KDA layer, one paged-attention
    kernel and three grouped matmuls an expert layer; a chunk is one
    chunk kernel a KDA layer beside the grouped matmuls, and no
    triangular solve."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import deepseek_v2
    from ray_tpu.ops import kda
    monkeypatch.setattr(deepseek_v2, "_on_tpu", lambda: True)
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs",
                           "ling-3.0-flash-ep8-d6.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])
    assert cache["lat"].shape == (1, e["kv_pages"] + 1, 64, 640)
    assert cache["kda"].shape == (5, rows, 32, 128, 128)
    assert cache["kda"].dtype == jnp.float32
    assert cache["conv"].shape == (5, rows, 3 * 12288)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3 << 29, mem.temp_size_in_bytes / 2**30
    held = sum(cache[k].size * cache[k].dtype.itemsize
               for k in ("lat", "kda", "conv"))
    assert arch.weight_bytes(c) + held \
        < mem.argument_size_in_bytes < arch.weight_bytes(c) + held + (1 << 26)
    assert mem.alias_size_in_bytes >= held       # donated through the step
    text = compiled.as_text()
    for name, order in (("lat", "3,2,1,0"), ("kda", "4,3,2,1,0"),
                        ("conv", "2,1,0")):
        held = "%s[%s]" % ({"float32": "f32", "bfloat16": "bf16"}[
            cache[name].dtype.name], ",".join(map(str, cache[name].shape)))
        layouts = set(re.findall(re.escape(held) + r"\{([\d,]+)", text))
        assert layouts == {order}, (name, layouts)         # never re-laid
        moved = [ln for ln in text.splitlines()
                 if re.search(r"= " + re.escape(held) + r"\S* copy\(", ln)]
        assert not moved, moved[:4]
    _nothing_of_the_selection(text, step)
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "tpu_custom_call" in ln]
    if step == "prefill_chunk":
        walked = [ln for ln in kernels if " %kda_chunk" in ln]
        assert len(walked) == cfg.n_kda, len(walked)
        assert len(kernels) == 3 * cfg.n_moe + cfg.n_kda, len(kernels)
        assert "InvertDiagBlocksLowerTriangular" not in text
        return
    stepped = [ln for ln in kernels if " %kda_step" in ln]
    assert len(stepped) == cfg.n_kda, len(stepped)
    assert len(_ragged_kernels(text)) == cfg.n_mla
    assert len(kernels) == cfg.n_kda + cfg.n_mla + 3 * cfg.n_moe


# The eleventh configuration (benchmarks/configs/glm-5-ep16-d5.json):
# five layers that each choose 2,048 keys a query out of a pool of two
# arrays of unequal width under one block table, 16 held of 256
# sigmoid-routed experts, built as the benchmark builds it, at its sizes
# (48 rows, a block table 544 pages wide).
@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_glm5_paged_step_compiles(chip, step, monkeypatch):
    """Both programs of glm-5-ep16-d5 as the chip runs them, within its
    memory: 7.83 GB of weights and 4.53 GB of pool (`lat` [5, P, 64,
    640] and `idx` [5, P, 64, 128] under one table) are resident and
    donated through the step; neither pool is ever re-laid or copied;
    THE SELECTION DOES NOT SORT (`lax.top_k` of 2,048 lowers to a full
    sort of [512, 34816] a layer): the only sorts left are the routers'
    top-8 of 256 and the expert walk's, none under a `dsa_` scope and
    none outside the expert layer; a tick's attention under the choice
    is one ragged kernel a layer on the branch that walks, handed the
    latent pool and the mask; a tick holds under 0.25 GiB of
    temporaries and a chunk under 1 GiB (its [512, 34816] float32 scores
    are 68 MiB; no array of [queries, heads, table width] stands)."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import deepseek_v2
    monkeypatch.setattr(deepseek_v2, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs", "glm-5-ep16-d5.json")) as f:
        c = json.load(f)
    arch = arch_of(c, bench)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    params = _on(chip, jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype)))
    cache = _on(chip, jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"])))
    rows, blocks = e["num_slots"], -(-e["max_seq"] // e["page_size"])
    assert (rows, blocks) == (48, 544)
    assert cache["lat"].shape == (5, e["kv_pages"] + 1, 64, 640)
    assert cache["idx"].shape == (5, e["kv_pages"] + 1, 64, 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    if step == "decode_tick":
        lowered = engine._paged_tick.lower(
            params, i32(rows), i32(rows), cache, i32(rows, blocks), cfg,
            with_logits=False)
    else:
        lowered = engine._prefill_chunk.lower(
            params, i32(1, e["prefill_chunk"]), i32(), cache,
            i32(1, blocks), cfg, slot=i32(), valid=i32())
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    limit = 1 << 28 if step == "decode_tick" else 1 << 30
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes / 2**30
    held = sum(cache[k].size * cache[k].dtype.itemsize
               for k in ("lat", "idx"))
    assert arch.weight_bytes(c) + held \
        < mem.argument_size_in_bytes < arch.weight_bytes(c) + held + (1 << 26)
    assert mem.alias_size_in_bytes >= held       # donated through the step
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14 << 30
    text = compiled.as_text()
    for name in ("lat", "idx"):
        held = "bf16[%s]" % ",".join(map(str, cache[name].shape))
        layouts = set(re.findall(re.escape(held) + r"\{([\d,]+)", text))
        assert len(layouts) == 1, (name, layouts)          # never re-laid
        moved = [ln for ln in text.splitlines()
                 if re.search(r"= " + re.escape(held) + r"\S* copy\(", ln)]
        assert not moved, moved[:4]
    for scope in ("dsa_index", "dsa_select", "dsa_attend"):
        assert scope in text, scope
    assert "mla_expand_attend" not in text \
        and "mla_absorb_attend" not in text
    sorts = [ln for ln in text.splitlines() if re.search(r" sort\(", ln)]
    assert all("/moe_route/" in ln or "/moe_experts/" in ln
               for ln in sorts), sorts[:2]
    assert len(sorts) <= 2 * cfg.n_moe, len(sorts)
    wide = re.findall(r"\[(?:512|48),(?:64|32),34816\]", text)
    assert not wide, wide[:4]
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "tpu_custom_call" in ln]
    # a tick's walk under the choice: one `ops/paged_attention.py`
    # kernel a layer, handed the latent pool as it lies and a line of
    # `keep` a block of 768 keys, beside the count of keys it weighed
    walks = _ragged_kernels(text)
    assert len(walks) == (cfg.n_layers if step == "decode_tick" else 0)
    lat = "bf16[%s]" % ",".join(map(str, cache["lat"].shape))
    assert all(lat in ln and "s32[48,46,768]" in ln
               and "s32[48,1,768]" in ln for ln in walks), walks[:1]
    assert len(kernels) == 3 * cfg.n_moe + len(walks), len(kernels)


@pytest.mark.parametrize("config", [
    "deepseek-v2-ep4-d5", "k-exaone-ep8-d5", "mimo-v2-flash-ep16-d7",
    "zaya1-8b-pp2-d20", "sdar-30b-a3b-pp8-d6"])
def test_the_expert_walks_widest_tile_fits_fast_memory(chip, config,
                                                       monkeypatch):
    """`routed_experts` of one expert layer at the configuration's tick
    (or block step) and chunk: every weight block a grid step holds is a
    whole contraction of at most `_GMM_TILE_BYTES`, and two of them
    beside two row tiles, two output tiles and the float32 accumulator
    stay under the 16 MiB a kernel gets by default, which the chip's
    compiler confirms by compiling both calls.  sdar's, which holds
    every expert, is ONE slab of all 4,096 pairs (no loop in the call);
    the four that hold a share walk slabs of as many pairs as tokens."""
    import json
    import os

    from benchmarks.lib.registry import arch_of
    from ray_tpu.models import deepseek_v2 as ds
    monkeypatch.setattr(ds, "_on_tpu", lambda: True)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        c = json.load(f)
    e = c["serving"]["engine"]
    cfg = arch_of(c, bench).build(c, e["max_seq"], remat=False)
    D, F, E, k = cfg.d_model, cfg.moe_d_ff, cfg.experts_held, cfg.top_k
    size = jnp.dtype(cfg.dtype).itemsize
    for K, N in ((D, F), (F, D)):
        tk, tn = ds._tiles(K, N, size)
        assert tk == K and N % tn == 0 and tk * tn * size \
            <= ds._GMM_TILE_BYTES, (K, N, tk, tn)
        rows = ds._GMM_ROWS
        vmem = 2 * tk * tn * size + 2 * rows * tk * size \
            + 2 * rows * tn * 4 + rows * tn * 4
        assert vmem <= 16 << 20, (K, N, tk, tn, vmem)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    experts = {"w_gate": arr(cfg.dtype, E, D, F),
               "w_up": arr(cfg.dtype, E, D, F),
               "w_down": arr(cfg.dtype, E, F, D)}
    block = getattr(cfg, "block_length", 1)
    for tokens in (e["num_slots"] * block, e["prefill_chunk"]):
        tm, S, most = ds._slab(tokens, k, cfg)
        if E == cfg.n_routed_experts:
            assert most == 1 and S == -(-tokens * k // tm) * tm
        else:
            assert S == -(-tokens // tm) * tm and most > 1
        text = jax.jit(lambda *a: ds.routed_experts(*a, cfg)).lower(
            experts, arr(cfg.dtype, tokens, D), arr(jnp.int32, tokens, k),
            arr(jnp.float32, tokens, k), arr(bool, tokens)
        ).compile().as_text()
        kernels = [ln for ln in text.splitlines()
                   if "custom-call(" in ln and "tpu_custom_call" in ln]
        assert len(kernels) == 3, len(kernels)
        # (a `searchsorted` is a loop of its own: the sort's group ends,
        # and the `repeat`s of the kernel's group metadata)
        loops = [ln for ln in text.splitlines()
                 if " while(" in ln and "searchsorted" not in ln]
        if config == "sdar-30b-a3b-pp8-d6":
            assert (S, tokens * k) == (4096, 4096) and not loops, loops[:2]
        else:
            assert bool(loops) == (most > 1)
