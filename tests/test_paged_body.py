"""The seam between the serving engine and a model body
(`decode.PagedBody`): a body built in this file is served by
`GenerationEngine` through a config that names it, with no file under
`ray_tpu/` knowing its name, and every real body's declaration is
complete.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (bailing_hybrid, decode, deepseek_v2, exaone_moe,
                            glm_moe_dsa, gpt, jamba, llama, mimo_v2_flash,
                            minicpm_sala, zaya)
from ray_tpu.serve.llm.engine import GenerationEngine

# ------------------------------------------------------------- a fake body

ROWS, PAGE, PAGES, VOCAB = 2, 4, 12, 11
PARAMS = {"step": jnp.int32(1)}


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    """All the engine reads off a config: a sequence bound, a dtype, the
    body's name (and `n_layers`, by the declared default of `n_attn`)."""
    vocab_size: int = VOCAB
    n_layers: int = 1
    max_seq: int = 32
    dtype: Any = jnp.float32
    bare: bool = False

    @property
    def paged_body(self) -> decode.PagedBody:
        return BARE_BODY if self.bare else TOY_BODY


def _toy_cache(cfg, num_pages, page_size, num_slots):
    pool = (cfg.n_layers, num_pages, page_size, 1, 2)
    return {"k": jnp.zeros(pool, cfg.dtype), "v": jnp.zeros(pool, cfg.dtype),
            "seen": jnp.zeros((num_slots,), jnp.int32)}


def _toy_step(params, tokens, pos, cache, block_tables, cfg, pad_lo=None,
              slot=None, valid=None):
    """One layer of nothing: the next token is this one + 1 (mod the
    vocabulary), whatever came before, so the logits are fixed by the
    input.  `seen` counts the tokens a decode row has taken: a chunk
    restarts `slot`'s at position 0 and adds its `valid` real tokens, a
    tick adds one to each row past position 0."""
    pos = jnp.asarray(pos)
    logits = jax.nn.one_hot((tokens + params["step"]) % cfg.vocab_size,
                            cfg.vocab_size)
    seen = cache["seen"]
    if pos.ndim == 0:
        seen = seen.at[slot].set(jnp.where(pos == 0, 0, seen[slot]) + valid)
    else:
        seen = seen + (pos > 0)
    return logits, dict(cache, seen=seen)


def _toy_check_paging(cfg, *, page_size, prefill_chunk, speculate_k):
    if speculate_k:
        raise NotImplementedError("the toy does not verify drafts")


def _toy_attn_keys(cfg, pos):
    held = int(np.asarray(pos).sum()) + len(pos)
    return held, held


# every optional hook left out: the declared defaults
BARE_BODY = decode.PagedBody(
    init_paged_cache=_toy_cache, paged_chunk_step=_toy_step,
    check_paging=_toy_check_paging, attn_keys=_toy_attn_keys)
# ...and the same with `seen` named as state of a decode row
TOY_BODY = dataclasses.replace(BARE_BODY, row_state_keys=("seen",))
ENGINE_KW = dict(num_slots=ROWS, page_size=PAGE, prefill_chunk=PAGE,
                 kv_pages=PAGES)


def _chain(prompt, n):
    return [(prompt[-1] + 1 + i) % VOCAB for i in range(n)]


def test_a_body_this_file_builds_is_served_by_the_engine():
    """Two requests through GenerationEngine on a body no file under
    ray_tpu/ knows: its tokens come out, its pool and its row state are
    counted from what it declares, and what cannot carry row state
    refuses it by its config's name."""
    cfg = ToyConfig()
    assert decode.paged_body(cfg) is TOY_BODY and TOY_BODY.has_row_state
    prompts = [[3, 1, 4, 1, 5, 9], [2, 7]]
    with GenerationEngine(PARAMS, cfg, enable_prefix_cache=False,
                          **ENGINE_KW) as eng:
        outs = [s.result(timeout=120) for s in
                [eng.submit(p, max_new_tokens=5) for p in prompts]]
        st = eng.stats()
        with pytest.raises(NotImplementedError,
                           match="kv_export .*per-row recurrent state "
                                 ".ToyConfig."):
            eng.kv_export(prompts[0])
    assert outs == [_chain(p, 5) for p in prompts]
    assert st.requests_completed == 2
    assert st.row_state_bytes == ROWS * 4
    assert st.kv_pool_bytes == PAGES * 2 * PAGE * 2 * 4   # k and v pages
    assert st.state_resets == 2          # one chunk at position 0 each
    assert st.prefill_tokens == sum(map(len, prompts))
    with pytest.raises(NotImplementedError,
                       match="the prefix cache .*per-row recurrent state "
                             ".ToyConfig."):
        GenerationEngine(PARAMS, cfg, enable_prefix_cache=True, **ENGINE_KW)
    with pytest.raises(NotImplementedError, match="verify drafts"):
        GenerationEngine(PARAMS, cfg, enable_prefix_cache=False,
                         speculate_k=2, **ENGINE_KW)


def test_a_body_that_leaves_every_hook_out_gets_the_declared_defaults():
    body, cfg = BARE_BODY, ToyConfig(bare=True)
    assert decode.paged_body(cfg) is body
    assert body.page_keys == ("k", "v") and body.row_state_keys == ()
    assert not body.has_row_state and not body.framed
    assert body.chunk_takes_row
    assert body.chunk_selects(cfg, 0) is False
    assert body.n_attn(cfg) == cfg.n_layers
    assert body.attn_keys_gathered is body.attn_keys_paged is None
    assert body.snapshot_counters is body.read_counters is None
    assert body.keys_gathered(cfg, 17, np.zeros(ROWS, np.int32), PAGE, 8) \
        == 17
    prompt = [3, 1, 4, 1, 5, 9]
    # no row state named: the prefix cache serves it, a second request
    # for the prompt shares its full page
    with GenerationEngine(PARAMS, cfg, **ENGINE_KW) as eng:
        outs = [eng.submit(prompt, max_new_tokens=4).result(timeout=120)
                for _ in range(2)]
        st = eng.stats()
        # ...and what frames pages refuses a body that is not `framed`
        with pytest.raises(NotImplementedError,
                           match="kv_export .*not K then V .ToyConfig"):
            eng.kv_export(prompt)
        assert eng._page_kshape is None and not eng._tiering
    assert outs == [_chain(prompt, 4)] * 2
    assert st.prefix_cache_hits == 1 and st.prefix_hit_tokens == PAGE
    assert st.row_state_bytes == 0 and st.state_resets == 0
    assert st.attn_keys_gathered == st.attn_keys_attended > 0
    assert st.attn_keys_context == st.attn_keys_resident
    assert st.attn_keys_gathered_paged == st.prefill_tokens_sparse == 0
    assert not any(v for k, v in st.to_dict().items()
                   if k.startswith("moe_"))


# --------------------------------------------------------- the real bodies

REAL = {
    "gpt": (lambda: gpt.GPTConfig(), decode.DENSE_BODY, False),
    "llama": (lambda: llama.LlamaConfig(), decode.DENSE_BODY, False),
    "minicpm_sala": (lambda: minicpm_sala.SalaConfig(
        mixer_types=(minicpm_sala.ATTN, minicpm_sala.LIN), max_seq=4096),
        minicpm_sala.BODY, True),
    "deepseek_v2": (lambda: deepseek_v2.DeepseekV2Config(max_seq=64),
                    deepseek_v2.BODY, False),
    "exaone_moe": (lambda: exaone_moe.ExaoneMoeConfig(max_seq=64),
                   exaone_moe.BODY, True),
    "jamba": (lambda: jamba.JambaConfig(max_seq=64), jamba.BODY, True),
    "mimo_v2_flash": (lambda: mimo_v2_flash.MimoV2FlashConfig(max_seq=64),
                      exaone_moe.BODY, True),
    "zaya": (lambda: zaya.ZayaConfig(max_seq=64), zaya.BODY, True),
    "bailing_hybrid": (lambda: bailing_hybrid.BailingHybridConfig(
        max_seq=64), bailing_hybrid.BODY, True),
    "glm_moe_dsa": (lambda: glm_moe_dsa.GlmMoeDsaConfig(max_seq=64),
                    glm_moe_dsa.BODY, False),
}


@pytest.mark.parametrize("name", sorted(REAL))
def test_every_real_body_declares_itself_whole(name):
    """The lookup finds the body, its required parts are functions, its
    optional ones functions or None, the cache entries it names exist
    and are named once, and "has row state" is "names row-state keys"."""
    make, want, row_state = REAL[name]
    cfg = make()
    body = decode.paged_body(cfg)
    assert body is want and isinstance(body, decode.PagedBody)
    for part in ("init_paged_cache", "paged_chunk_step", "check_paging",
                 "attn_keys", "chunk_selects", "n_attn"):
        assert callable(getattr(body, part)), part
    for hook in ("attn_keys_gathered", "attn_keys_paged",
                 "snapshot_counters", "read_counters"):
        assert getattr(body, hook) is None or callable(getattr(body, hook))
    assert (body.snapshot_counters is None) == (body.read_counters is None)
    assert body.has_row_state == bool(body.row_state_keys) == row_state
    assert 0 < body.n_attn(cfg) <= cfg.n_layers
    page = cfg.block if name == "minicpm_sala" else 16
    cache = jax.eval_shape(
        lambda: decode.init_paged_cache(cfg, 3, page, 2))
    named = body.page_keys + body.row_state_keys
    assert body.page_keys and set(named) <= set(cache)
    assert len(set(named)) == len(named)
    assert all(cache[k].shape[1] == 3 for k in body.page_keys)
    assert all(cache[k].shape[1] == 2 for k in body.row_state_keys)
    if body.framed:
        assert body is decode.DENSE_BODY and not body.chunk_takes_row
        assert body.page_keys == ("k", "v") and not body.has_row_state
        assert cache["k"].shape == cache["v"].shape \
            == (cfg.n_layers, 3, page, decode._kv_heads(cfg), cfg.head_dim)
