"""SDAR-MoE (`sdar_moe`) at toy widths on the CPU: generation by
diffusion over blocks through the engine's own two programs (chunks
with `valid`, then block steps through the cache) against one forward of
the plain reference at every step of every block, at sizes that keep
every ratio (8 query heads on 2 key-value heads, 16 experts top-4, a
block of 4 in 4 steps); a prompt split at every boundary and every
`L mod 4`; two rows at different steps of their blocks in one program
and a slot that changes hands; the controls a comparison must catch; the
engine's stream against a plain loop of the family's generation over the
reference, token for token, outputs that end inside a block among them;
what the engine counts; the refusals by name; the benchmark's
architecture files against the program; and the toy configuration
served to `correct`."""

import ast
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, sdar_moe
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ARCH_DIR = os.path.join(BENCH, "archs", "sdar_moe")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

L, HEADS, E, D, V, B, MASK = 3, 8, 16, 64, 96, 4, 95
C = {
    "name": "toy-sdar", "arch": "sdar_moe", "attention_bias": False,
    "decoder_sparse_step": 1, "head_dim": 8, "hidden_act": "silu",
    "hidden_size": D, "intermediate_size": 128,
    "max_position_embeddings": 4096, "max_window_layers": L,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": HEADS, "num_experts": E,
    "num_experts_per_tok": 4, "num_hidden_layers": L,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": V, "torch_dtype": "float32",
    "assumed": {"block_length": B, "denoising_steps": B,
                "remasking": "low_confidence_static",
                "mask_token_id": MASK},
    "serving": {"engine": {"num_slots": 3, "max_seq": 128, "page_size": 4,
                           "kv_pages": 96, "prefill_chunk": 12,
                           "enable_prefix_cache": False},
                "check": {"prompt_len": 22, "decode_tokens": 3,
                          "short": {"prompt_len": 6, "blocks": 5},
                          "tolerance": {"max_abs_diff": 1e-4,
                                        "mean_abs_diff": 1e-5}}}}
ROWS = 3
PAD = 64       # one length for every reference forward of a test


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "sdar_" + name, os.path.join(ARCH_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arch():
    from benchmarks.lib.registry import arch_of
    return arch_of(C, BENCH)


@pytest.fixture(scope="module")
def reference():
    return _load("reference")


@pytest.fixture(scope="module")
def model(arch):
    """The seeded weights as they are drawn, but for the norms' gains,
    which are bumped so a missing one shows."""
    cfg = arch.build(C, C["serving"]["engine"]["max_seq"], remat=False)
    params = arch.init(cfg, jax.random.PRNGKey(7), jnp.float32)
    bump = iter(jax.random.split(jax.random.PRNGKey(8), 64))

    def bumped(path, w):
        if path[-1].key in ("ln1", "ln2", "ln_f", "qn", "kn"):
            return w + 0.1 * jax.random.normal(next(bump), w.shape)
        return w
    return cfg, jax.tree_util.tree_map_with_path(bumped, params)


@pytest.fixture(scope="module")
def ref_logits(model, reference):
    """(tokens of any length <= PAD) -> the reference's logits: whole
    blocks through one compiled forward, padded behind them, where no
    position sees the pad; a prompt that ends inside a block at its own
    length (its last positions see the block's end, and nothing stands
    there yet)."""
    _, params = model
    fwd = jax.jit(lambda t: reference.forward(params, t, C))

    def at(tokens):
        seq = np.zeros((PAD if len(tokens) % B == 0 else len(tokens),),
                       np.int32)
        seq[:len(tokens)] = tokens
        return np.asarray(fwd(jnp.asarray(seq)))[:len(tokens)]
    return at


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, MASK, size=n).astype(np.int32)


class Driver:
    """The engine's two jitted programs over one cache, driven by hand
    as the engine's admission and block turn do: the blocks (tokens,
    which positions are masked) are kept here and handed in every step
    (`take_host` everywhere), the schedule is the engine's."""

    def __init__(self, cfg, params, psz, chunk, pages=96, nblk=32):
        self.cfg, self.params, self.psz, self.chunk = cfg, params, psz, chunk
        self.cache = decode.init_paged_cache(cfg, pages + 1, psz, ROWS)
        self.bt = np.zeros((ROWS, nblk), np.int32)
        self.pos = np.zeros((ROWS,), np.int32)
        self.tok = np.zeros((ROWS, B), np.int32)
        self.masked = np.zeros((ROWS, B), bool)
        self.prefix = [None] * ROWS     # the final tokens before the block
        self.next_page = 1

    def admit(self, slot, toks, total):
        """Prefill `toks` into fresh pages for `total` tokens, chunk by
        chunk (the last one padded, told its `valid`), then open the
        first block in row `slot`.  Returns the prompt's logits."""
        n = -(-total // self.psz)
        row = np.zeros((self.bt.shape[1],), np.int32)
        row[:n] = np.arange(self.next_page, self.next_page + n)
        self.next_page += n
        rows = []
        for s in range(0, len(toks), self.chunk):
            real = toks[s:s + self.chunk]
            chunk = np.zeros((1, self.chunk), np.int32)
            chunk[0, :len(real)] = real
            logits, self.cache = engine_mod._prefill_chunk(
                self.params, jnp.asarray(chunk), jnp.int32(s), self.cache,
                jnp.asarray(row[None]), self.cfg, slot=jnp.int32(slot),
                valid=jnp.int32(len(real)))
            rows.append(np.asarray(logits[0, :len(real)]))
        start = len(toks) // B * B
        fixed = len(toks) - start
        self.bt[slot], self.pos[slot] = row, start
        self.tok[slot] = MASK
        self.tok[slot, :fixed] = toks[start:]
        self.masked[slot] = np.arange(B) >= fixed
        self.prefix[slot] = [int(t) for t in toks[:start]]
        return np.concatenate(rows)

    def leave(self, slot):
        self.bt[slot], self.pos[slot] = 0, 0
        self.tok[slot], self.masked[slot] = 0, False
        self.prefix[slot] = None

    def step(self):
        """One block step of every live row.  Returns {slot: (the final
        tokens before its block, the block as the step saw it, the
        step's logits [B, V])}; a row whose block was full has now
        written it and moves on to a block of masks."""
        live = [s for s in range(ROWS) if self.prefix[s] is not None]
        tokens, masked, logits, self.cache = engine_mod._paged_block_step(
            self.params, jnp.zeros((ROWS, B), jnp.int32),
            jnp.zeros((ROWS, B), bool), jnp.asarray(self.tok),
            jnp.asarray(self.masked), jnp.ones((ROWS,), bool),
            jnp.asarray(self.pos), self.cache, jnp.asarray(self.bt),
            self.cfg, with_logits=True)
        logits = np.asarray(logits)
        assert np.isfinite(logits).all()       # idle rows too
        out = {}
        for s in live:
            out[s] = (list(self.prefix[s]), self.tok[s].copy(), logits[s])
            if not self.masked[s].any():       # it was the writing forward
                self.prefix[s] += [int(t) for t in self.tok[s]]
                self.pos[s] += B
                self.tok[s], self.masked[s] = MASK, True
            else:
                left = np.asarray(masked[s])
                assert left.sum() == self.masked[s].sum() - 1   # one a step
                self.tok[s], self.masked[s] = np.asarray(tokens[s]), left
        return out


def _diff(ref_logits, forward):
    prefix, block, got = forward
    want = ref_logits(prefix + [int(t) for t in block])[len(prefix):]
    return float(np.abs(got - want).max())


# ------------------------------------ the engine's programs = one forward

CASES = {
    # page, chunk, prompt, steps: L mod 4 = 1: two whole chunks and a
    # padded third whose last block is cut at the prompt's end
    "chunk-12-page-4": (4, 12, 29, 16),
    "chunk-16-page-8": (8, 16, 38, 12),      # L mod 4 = 2
    "whole-blocks": (4, 12, 36, 11),         # the first block all masks
    "short-prompt": (4, 12, 7, 14),          # L mod 4 = 3, one padded chunk
}


@pytest.mark.parametrize("case", list(CASES))
def test_chunks_then_block_steps_are_one_reference_forward_each(
        model, ref_logits, case):
    """Every prompt position's logits, then the four positions of every
    step of every block (masks standing in the input, and the forward
    that writes a full block), equal the reference's forward of the same
    tokens: block-causal chunks with `valid`, keys rewritten a step,
    final keys read by later blocks."""
    cfg, params = model
    psz, chunk, n_prompt, steps = CASES[case]
    toks = _tokens(n_prompt, seed=n_prompt)
    drv = Driver(cfg, params, psz, chunk)
    got = drv.admit(0, toks, n_prompt + B * steps)
    assert np.abs(got - ref_logits(toks)).max() < 2e-5
    writes = 0
    for _ in range(steps):
        forward = drv.step()[0]
        assert _diff(ref_logits, forward) < 2e-5
        writes += MASK not in forward[1]
    assert writes >= 2          # later blocks read written ones


@pytest.mark.parametrize("n_prompt", range(12, 26))
def test_a_prompt_split_at_every_boundary_and_every_tail(model, ref_logits,
                                                         n_prompt):
    """Prompts of 12..25 tokens over chunks of 12 and pages of 4: every
    `L mod 4`, a chunk boundary inside and at the edge of the prompt; the
    first block's steps see the prompt's tail fixed in it."""
    cfg, params = model
    toks = _tokens(n_prompt, seed=100 + n_prompt)
    drv = Driver(cfg, params, 4, 12)
    got = drv.admit(0, toks, n_prompt + 2 * B)
    assert np.abs(got - ref_logits(toks)).max() < 2e-5
    first = drv.step()[0]
    assert list(first[1][:n_prompt % B]) == list(toks[n_prompt // B * B:])
    assert (first[1][n_prompt % B:] == MASK).all()
    assert _diff(ref_logits, first) < 2e-5
    for _ in range(B + 2):
        assert _diff(ref_logits, drv.step()[0]) < 2e-5


def test_two_rows_at_different_steps_and_a_slot_that_changes_hands(
        model, ref_logits):
    """Row 0 is two steps into its block when row 2 joins; they step
    together (one denoising, one writing, in one program); row 0 leaves
    and another sequence takes its slot and pages anew while row 2 goes
    on: every forward of every row is its own sequence's."""
    cfg, params = model
    drv = Driver(cfg, params, 4, 12)
    a, b, c = _tokens(21, 1), _tokens(14, 2), _tokens(9, 3)
    drv.admit(0, a, 21 + 4 * B)
    for _ in range(2):
        assert _diff(ref_logits, drv.step()[0]) < 2e-5
    drv.admit(2, b, 14 + 4 * B)
    kinds = set()
    for _ in range(7):
        out = drv.step()
        assert set(out) == {0, 2}
        kinds.add((MASK in out[0][1], MASK in out[2][1]))
        for forward in out.values():
            assert _diff(ref_logits, forward) < 2e-5
    assert (False, True) in kinds and (True, False) in kinds
    drv.leave(0)
    drv.admit(0, c, 9 + 3 * B)
    for _ in range(8):
        out = drv.step()
        assert out[0][0][:8] == [int(t) for t in c[:8]]
        for forward in out.values():
            assert _diff(ref_logits, forward) < 2e-5


# ------------------------------------------------- controls

CONTROLS = {
    "the mask causal inside a block": {"_causal_in_block": True},
    "the logits shifted by one": {"_shift_logits": True},
    "top-3 for top-4": {"_top_k": 3},
    "no renormalisation": {"_no_renorm": True},
    "q/k norm dropped": {"_no_qk_norm": True},
}


@pytest.fixture(scope="module")
def served_logits(model, arch):
    """The check's own rows at toy size, through an idle engine's
    programs: 22 prompt tokens, then 3 blocks (13 forwards)."""
    from benchmarks.lib import checks
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=4,
                           prefill_chunk=12, kv_pages=96,
                           enable_prefix_cache=False)
    prompt = checks.seeded_prompt(5, V, 22)
    with checks.borrowed_pages(eng, 20 + 3 * B) as bt_row:
        rows = arch.prefill(eng, prompt, bt_row)
        forwards = arch.block_by_block(eng, prompt, 3, bt_row)
    with checks.borrowed_pages(eng, 20 + 3 * B) as bt_row:
        arch.prefill(eng, prompt, bt_row)
        unwritten = arch.block_by_block(eng, prompt, 3, bt_row,
                                        skip_writing=True)
    return prompt, rows, forwards, unwritten


@pytest.mark.parametrize("control", list(CONTROLS) + [
    "float8 matmuls", "a block's writing forward skipped"])
def test_each_control_is_another_model(model, arch, served_logits, control):
    """What the program serves is the reference to 2e-5; each control
    is a hundred times further, by the largest difference."""
    _, params = model
    prompt, rows, forwards, unwritten = served_logits
    got = np.concatenate(rows + [lg for _, _, lg in forwards])
    truth = arch.reference_rows(params, C, prompt, forwards)
    assert len(forwards) == 3 + 5 + 5 and got.shape == (22 + 13 * B, V)
    ours = np.abs(got - truth).max()
    assert ours < 2e-5
    if control == "a block's writing forward skipped":
        # the program run wrong against the reference run right: later
        # blocks read keys computed while a position was still a mask
        assert len(unwritten) == 2 + 4 + 4
        got = np.concatenate([lg for _, _, lg in unwritten])
        other = arch.reference_rows(params, C, prompt, unwritten)[22:]
        assert np.abs(got[:2 * B] - other[:2 * B]).max() < 2e-5  # block one
        assert np.abs(got - other).max() > 100 * ours
        return
    if control == "float8 matmuls":
        other = arch.reference_rows(params, C, prompt, forwards,
                                    round_to="float8_e4m3fn")
    else:
        other = arch.reference_rows(params, dict(C, **CONTROLS[control]),
                                    prompt, forwards)
    assert np.abs(other - truth).max() > 100 * ours


# ------------------------------------------------- the engine

PROMPTS = (22, 5, 16, 9, 31, 12)
NEW = (9, 4, 13, 1, 6, 8)


def _forwards(n_prompt, n_new):
    """(denoising forwards, writing forwards) the static schedule runs
    for one request: a block of m masks costs m and then one that writes
    it, but for the last block's, which nothing reads."""
    fixed, end = n_prompt % B, n_prompt + n_new
    blocks = -(-end // B) - n_prompt // B
    return blocks * B - fixed, blocks - 1


@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=4,
                           prefill_chunk=12, kv_pages=96,
                           enable_prefix_cache=False)
    yield eng
    eng.stop()


def test_the_engines_stream_is_the_familys_generation_token_for_token(
        model, served, reference):
    """Six requests on three rows (slots change hands; prompts of every
    `L mod 4`; outputs that end inside a block, one of a single token):
    each stream equals a plain loop of the family's generation over the
    reference, and the engine's counts are the schedule's."""
    _, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in PROMPTS]
    outs = [s.result(timeout=300) for s in
            [served.submit(p, max_new_tokens=m)
             for p, m in zip(prompts, NEW)]]
    for p, m, out in zip(prompts, NEW, outs):
        assert out == reference.generate(params, p, m, C)
    after = served.stats().to_dict()
    gain = {k: v - before[k] for k, v in after.items()
            if isinstance(v, (int, float))}
    denoise = sum(_forwards(p, m)[0] for p, m in zip(PROMPTS, NEW))
    writes = sum(_forwards(p, m)[1] for p, m in zip(PROMPTS, NEW))
    assert gain["tokens_generated"] == sum(NEW)
    assert gain["block_positions_fixed"] == denoise
    assert gain["block_row_writes"] == writes
    assert gain["block_row_forwards"] == denoise + writes
    assert gain["block_columns"] == B * (denoise + writes)
    assert 0 < gain["block_steps"] <= gain["block_row_forwards"]
    assert gain["loop_turns_ahead"] > 0       # read one turn late
    assert gain["prefill_tokens"] == sum(PROMPTS)
    # every column of a live row is routed, and every pair is held
    ran = sum(PROMPTS) + B * (denoise + writes)
    assert gain["moe_pairs_routed"] == gain["moe_pairs_local"] \
        == ran * L * C["num_experts_per_tok"]
    # ... so a call is one slab and reads each expert it touches once
    assert gain["moe_expert_reads"] == gain["moe_experts_touched"] > 0
    assert gain["attn_keys_attended"] == gain["attn_keys_resident"] > 0


def test_a_block_steps_expert_layer_is_one_slab_in_line(model):
    """The expert layer at the block body's toy shape (3 rows x 4
    columns, top-4 of 16, every expert held) against a masked loop over
    tokens: the 48 pairs are one slab of `routed_experts` in line (no
    walk), a dead row's columns get nothing and count nowhere, and the
    layer's counters say each touched expert was read once."""
    from ray_tpu.models import deepseek_v2 as ds
    cfg, params = model
    # weights large enough that a missing or doubled pair shows
    lp = dict(params["layers"][1], experts=jax.tree_util.tree_map(
        lambda a: 16 * a, params["layers"][1]["experts"]))
    n, k = ROWS * B, cfg.top_k
    assert ds._slab(n, k, cfg) == (48, 48, 1)
    x = jax.random.normal(jax.random.PRNGKey(31), (n, D))
    live = jnp.repeat(jnp.asarray([True, False, True]), B)
    got, counts = sdar_moe._ffn(lp, x, live,
                                [jnp.int32(0)] * len(ds.COUNTERS), cfg)
    h = sdar_moe._em._rms(x, lp["ln2"], cfg)
    ids, w = (np.asarray(a) for a in sdar_moe.route(lp["router"], h, cfg))
    h32 = np.asarray(h, np.float32)
    gate, up, down = (np.asarray(lp["experts"][m], np.float32)
                      for m in ("w_gate", "w_up", "w_down"))
    want, on = np.asarray(x, np.float32).copy(), np.zeros((E,), np.int64)
    for t in np.flatnonzero(np.asarray(live)):
        for e, wt in zip(ids[t], w[t]):
            a = h32[t] @ gate[e]
            want[t] += wt * ((a / (1 + np.exp(-a)) * (h32[t] @ up[e]))
                             @ down[e])
            on[e] += 1
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert np.abs(want - np.asarray(x)).max() > 1
    np.testing.assert_array_equal(np.asarray(got)[B:2 * B],
                                  np.asarray(x)[B:2 * B])
    counts = dict(zip(ds.COUNTERS, (int(c) for c in counts)))
    assert counts["pairs_routed"] == counts["pairs_local"] == 2 * B * k
    assert counts["pairs_worked"] == 48
    assert counts["expert_reads"] == counts["experts_touched"] \
        == (on > 0).sum() > 1


def test_a_shared_prefix_is_served_from_the_radix_cache_equal(model,
                                                              reference):
    """Whole prompt pages are final after prefill (a block straddles no
    page), so the prefix cache shares them: a second prompt that shares
    two pages hits them and streams what it streams without the cache."""
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=4,
                           prefill_chunk=12, kv_pages=96,
                           enable_prefix_cache=True)
    try:
        head = _tokens(10, 40).tolist()
        first = head + _tokens(5, 41).tolist()
        second = head + _tokens(9, 42).tolist()
        assert eng.submit(first, max_new_tokens=6).result(timeout=300) \
            == reference.generate(params, first, 6, C)
        assert eng.submit(second, max_new_tokens=7).result(timeout=300) \
            == reference.generate(params, second, 7, C)
        stats = eng.stats()
        assert stats.prefix_cache_hits == 1 and stats.prefix_hit_tokens == 8
    finally:
        eng.stop()


@pytest.mark.parametrize("what", [
    "temperature", "short_prompt", "kv_tiering", "kv_export", "kv_import",
    "session", "session_resurrect", "migrate_local", "speculation",
    "page_size", "prefill_chunk", "one_token_step"])
def test_what_a_block_body_cannot_serve_refuses_by_name(model, served, what):
    cfg, params = model
    kw = dict(num_slots=ROWS, page_size=4, prefill_chunk=12,
              enable_prefix_cache=False)
    missing = "diffusion over blocks .SdarMoeConfig."
    if what == "temperature":
        with pytest.raises(NotImplementedError, match=missing):
            served.submit([1, 2, 3, 4, 5], max_new_tokens=2, temperature=0.7)
    elif what == "short_prompt":
        with pytest.raises(ValueError, match="shorter than one block"):
            served.submit([1, 2, 3], max_new_tokens=2)
    elif what == "kv_tiering":
        with pytest.raises(NotImplementedError, match=missing):
            GenerationEngine(params, cfg, kv_tiering=True,
                             **dict(kw, enable_prefix_cache=True))
    elif what == "kv_export":
        with pytest.raises(NotImplementedError, match=missing):
            served.kv_export([1, 2, 3, 4])
    elif what == "kv_import":
        with pytest.raises(NotImplementedError, match=missing):
            served.kv_import([1, 2, 3, 4], np.zeros(1), np.zeros(1))
    elif what == "session":
        with pytest.raises(NotImplementedError, match=missing):
            served.submit([1, 2, 3, 4], max_new_tokens=2, session_id="s")
    elif what == "session_resurrect":
        with pytest.raises(NotImplementedError, match=missing):
            served.session_resurrect("s")
    elif what == "migrate_local":
        with pytest.raises(NotImplementedError, match=missing):
            kv_transfer.migrate_local(served, served, [1, 2, 3, 4])
    elif what == "speculation":
        with pytest.raises(NotImplementedError, match="diffusion over "
                                                      "blocks"):
            GenerationEngine(params, cfg, speculate_k=2, **kw)
    elif what == "page_size":
        with pytest.raises(ValueError, match="straddles neither"):
            GenerationEngine(params, cfg, **dict(kw, page_size=6,
                                                 prefill_chunk=12))
    elif what == "prefill_chunk":
        with pytest.raises(ValueError, match="straddles neither"):
            GenerationEngine(params, cfg, **dict(kw, prefill_chunk=10))
    else:
        cache = decode.init_paged_cache(cfg, 9, 4, ROWS)
        with pytest.raises(ValueError, match="4 columns a row"):
            decode.paged_chunk_step(
                params, jnp.zeros((ROWS, 1), jnp.int32),
                jnp.zeros((ROWS,), jnp.int32), cache,
                jnp.zeros((ROWS, 8), jnp.int32), cfg)


def test_the_body_declares_its_block_and_nothing_else_changes():
    cfg = sdar_moe.SdarMoeConfig(max_seq=64)
    body = decode.paged_body(cfg)
    assert (body.block, body.mask_token) == (4, 151669)
    assert not body.framed and not body.has_row_state
    assert (decode.DENSE_BODY.block, decode.DENSE_BODY.mask_token) \
        == (1, None)
    assert cfg.kind.flat and cfg.kind.qk_norm
    with pytest.raises(ValueError, match="block_length"):
        sdar_moe.SdarMoeConfig(max_seq=64, block_length=1)


# ---------------------------------------------- the benchmark's files

def _real_config():
    with open(os.path.join(BENCH, "configs",
                           "sdar-30b-a3b-pp8-d6.json")) as f:
        return json.load(f)


def test_costs_against_hand_counts(arch):
    c = _real_config()
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2
    expert = 3 * 2048 * 768
    layer = attn + 2048 * 128 + 128 * expert
    head = 151936 * 2048
    small = 6 * (2 * 2048 + 2 * 128) + 2048
    assert arch.attention_params(c) == attn == 18_874_368
    assert arch.expert_params(c) == expert
    assert arch.matmul_params(c) == 6 * layer + head
    assert arch.total_params(c) == 6 * layer + 2 * head + small
    f32 = 6 * 2048 * 128 + small
    assert arch.weight_bytes(c) == 2 * (6 * layer + 2 * head) + 4 * f32 \
        - 2 * 6 * 2048 * 128
    assert abs(arch.weight_bytes(c) / 8.72e9 - 1) < 0.005
    assert arch.kv_bytes_per_token(c) == 6 * 2 * 4 * 128 * 2 == 12288
    assert arch.experts_touched(c, 512) > 127.9
    # a block step of 128 rows at 1,000 tokens a row
    step = arch.block_step(c, 128, 512, 128_000)
    keys = 128_000 + 512
    attn_flops = 6 * 2 * 32 * 2 * 128 * 4 * keys
    assert arch.block_attn(c, 512, 128_000) == {
        "flops": attn_flops, "bytes": 6 * 2048 * keys}
    fixed = 6 * attn + head
    assert step["flops"] == 2 * fixed * 512 + 6 * 2 * 2048 * 128 * 512 \
        + 6 * 2 * expert * 4096 + attn_flops
    experts_read = 6 * arch.experts_touched(c, 512) * expert * 2
    assert step["bytes"] == pytest.approx(
        fixed * 2 + 512 * 2048 * 2 + 12288 * 512
        + 6 * (2048 * 128 * 4 + 512 * (2048 * 2 + 128 * 4))
        + experts_read + 6 * 4096 * 2 * 2048 * 2 + 6 * 2048 * keys)
    assert 9.5e9 < step["bytes"] < 10.0e9        # the ISSUE's ~9.7 GB
    assert arch.decode_tick(c, 128, 128_000) == step
    assert arch.block_step(c, 0, 0, 0)["flops"] == 0
    # a chunk needs no head, whatever the caller says of its place
    assert arch.prefill_chunk(c, 512, 1024, True) \
        == arch.prefill_chunk(c, 512, 1024, False)
    chunk = arch.prefill_chunk(c, 512, 1024, False)
    assert chunk["flops"] > 2 * 6 * attn * 512 + 6 * 2 * expert * 4096
    assert arch.attn_global(c, 512, 1024)["flops"] \
        == 6 * 2 * 32 * 2 * 128 * 512 * (1024 + 258)
    with pytest.raises(NotImplementedError, match="serves only"):
        arch.train_flops_per_token(c, 4096)


def test_the_weights_are_what_the_yardstick_counts(arch):
    c = _real_config()
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    shapes = jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype))
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shapes))
    assert held == arch.weight_bytes(c)
    cache = jax.eval_shape(lambda: decode.init_paged_cache(cfg, 9, 64, 2))
    assert cache["k"].shape == cache["v"].shape == (6, 9, 64, 512)
    assert (cache["k"].size + cache["v"].size) * 2 \
        == 9 * 64 * arch.kv_bytes_per_token(c)


def test_the_benchmarks_init_is_the_programs_but_for_the_qk_norms(arch,
                                                                  model):
    cfg, _ = model
    key = jax.random.PRNGKey(11)
    ours = jax.jit(lambda k: arch.init(cfg, k, jnp.bfloat16))(key)
    theirs = jax.jit(lambda k: sdar_moe.init_params(cfg, k, jnp.bfloat16)
                     )(key)
    assert jax.tree_util.tree_structure(ours) \
        == jax.tree_util.tree_structure(theirs)
    flat = jax.tree_util.tree_flatten_with_path
    for (path, a), (_, b) in zip(flat(ours)[0], flat(theirs)[0]):
        assert a.dtype == b.dtype
        if path[-1].key in ("qn", "kn"):
            assert bool((a == 2.0).all()) and bool((b == 1.0).all())
        else:
            assert bool((a == b).all())
    lp = ours["layers"][1]
    assert isinstance(ours["layers"], tuple) and "wlm" in ours
    assert lp["experts"]["w_gate"].shape == (E, D, 32)
    assert lp["router"].dtype == jnp.float32 and "shared" not in lp
    assert lp["wkv"].shape == (D, 2, 2, 8)


def test_the_reference_imports_jax_alone():
    with open(os.path.join(ARCH_DIR, "reference.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add("." if node.level else node.module.split(".")[0])
    assert imported <= {"__future__", "jax"}, imported


def test_the_architecture_fails_by_name_on_a_program_without_the_model(
        monkeypatch):
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "ray_tpu.models.sdar_moe"
        else real(name, *a))
    spec = importlib.util.spec_from_file_location(
        "sdar_arch_probe", os.path.join(ARCH_DIR, "__init__.py"),
        submodule_search_locations=[ARCH_DIR])
    mod = importlib.util.module_from_spec(spec)
    with pytest.raises(ImportError, match="ray_tpu.models.sdar_moe"):
        spec.loader.exec_module(mod)


def test_no_other_configuration_imports_the_model():
    """Nothing this model brings runs at import or at replica start for
    another configuration: `ray_tpu.models` does not import it, nor do
    the engine, decode, or the modules whose functions it runs."""
    code = ("import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
            "sys.path.insert(0, %r); "
            "from benchmarks.lib.registry import arch_of; arch_of({}); "
            "arch_of({'arch': 'exaone_moe'}); "
            "arch_of({'arch': 'deepseek_v2'}); "
            "import ray_tpu.models.exaone_moe; "
            "bad = [m for m in sys.modules if 'sdar' in m]; "
            "assert not bad, bad" % REPO)
    import subprocess
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_the_configuration_file_holds_the_catalogs_numbers(arch):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    row = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    c = _real_config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 48}
    assert c["num_hidden_layers"] == 6 and 48 % 6 == 0
    assert (c["num_experts"], c["num_experts_per_tok"], c["vocab_size"]) \
        == (128, 8, 151936)
    assert "first of 8 pipeline stages" in c["stands_for"]
    for key in ("assumed", "assumed_why", "departures", "resident_bytes",
                "reduced_why"):
        assert c[key], key
    assert c["assumed"] == {"block_length": 4, "denoising_steps": 4,
                            "remasking": "low_confidence_static",
                            "mask_token_id": 151669}
    for point in ("torch_dtype", "block_length", "remasking",
                  "mask_token_id", "q/k norm", "the mask", "seeded weights"):
        assert any(point in k for k in c["assumed_why"]), point
    for left_out in ("sampling", "dynamic-threshold", "NOT FUSED"):
        assert any(left_out in d for d in c["departures"]), left_out
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    assert cfg == sdar_moe.SdarMoeConfig(
        max_seq=c["serving"]["engine"]["max_seq"], n_layers=6)
    assert (cfg.experts_held, cfg.block_length) == (128, 4)


def test_the_new_cells_files_load_through_the_registry():
    from benchmarks.lib.registry import Registry
    reg = Registry(REPO)
    cell = reg.cell("sdar-blockgen")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("sdar-30b-a3b-pp8-d6", "blockgen", 1)
    c, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    assert c["arch"] == "sdar_moe"
    assert (mix["loop"], mix["clients"], mix["block"], mix["blocks"],
            mix["warmup_first_tokens"], mix["trace_seconds"]) \
        == ("closed", 256, 128, 16, 128, 6)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 64, "max": 2048}
    assert mix["output_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.4, "min": 256, "max": 2048}
    e = c["serving"]["engine"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= e["max_seq"] == 4096
    assert mix["block"] == e["num_slots"] == 128
    assert e["max_queue_len"] >= mix["clients"]
    assert e["page_size"] % 4 == 0 and e["prefill_chunk"] % e["page_size"] == 0
    check = c["serving"]["check"]
    assert check["prompt_len"] % e["prefill_chunk"] \
        and check["prompt_len"] % 4           # neither whole chunks nor blocks
    # ...and a second sequence whose keys are nearly all a block step's,
    # across a page's edge
    short = check["short"]
    assert short["prompt_len"] < 8 and short["prompt_len"] % 4 \
        and short["prompt_len"] + 4 * short["blocks"] > e["page_size"]
    names = {m["name"] for m in reg.metrics_for("sdar-blockgen", "per_layer")}
    assert {"block_step_ms.tput", "block_step_roofline.tput",
            "forwards_per_token.tput", "block_write_share.tput",
            "prefill_chunk_roofline.tput", "tick_ahead_share.tput",
            "expert_rows_worked_ratio.tput", "replica_start_s"} <= names
    assert not {"decode_tick_ms.tput", "paged_tick_roofline.tput",
                "row_state_gb.tput"} & names
    assert {m["name"] for m in reg.metrics_for(
        "sdar-blockgen", "end_to_end")} == {"out_tok_per_s", "setup_s"}
    for name in names:
        reg.reader(reg.metric(name)["reader"])
    s0 = {"block_row_forwards": 100, "block_row_writes": 20,
          "tokens_generated": 80}
    s1 = {"block_row_forwards": 600, "block_row_writes": 120,
          "tokens_generated": 480}
    obs = {"stats0": s0, "stats1": s1}
    per = reg.metric("forwards_per_token.tput")
    assert reg.reader(per["reader"])(obs, **per["args"]) == 1.25
    share = reg.metric("block_write_share.tput")
    assert reg.reader(share["reader"])(obs, **share["args"]) == 20.0
    # a parent without the counters reads nothing, quietly
    for spec in (per, share):
        assert reg.reader(spec["reader"])(
            {"stats0": {}, "stats1": {}}, **spec["args"]) is None


def test_the_block_steps_roofline_reads_the_programs_own_counts(arch):
    """`readers/roofline_block_step.py`: rows, columns and depths of the
    mean traced step from the engine's counters between the stats
    samples inside the traced window, the steps' times from the trace;
    nothing from a parent that has neither."""
    from benchmarks.lib.costs import min_time
    from benchmarks.lib.peaks import peaks_for
    from benchmarks.lib.registry import Registry
    reg = Registry(REPO)
    c = _real_config()
    spec = reg.metric("block_step_roofline.tput")
    read = reg.reader(spec["reader"])

    def sample(up, steps):
        return {"uptime_s": up, "block_steps": steps,
                "block_row_forwards": 120 * steps,
                "block_columns": 480 * steps,
                "attn_keys_resident": 6 * (120_000 + 480) * steps}
    obs = {"arch": arch, "config": c, "t_w": 1000.0,
           "stats0": sample(50.0, 100), "stats1": sample(95.0, 2000),
           "trace_t0": 1018.0, "trace_t1": 1024.0,
           "samples": [sample(50.0 + t, 100 + 40 * t) for t in range(0, 45)],
           "replica_info": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"jit__paged_block_step": [0.025] * 200}}}
    least = min_time(arch.block_step(c, 120, 480, 120_000),
                     peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert read(obs, **spec["args"]) == pytest.approx(
        100 * least["seconds"] / 0.025)
    assert obs["notes"]["jit__paged_block_step_mean_step"]["rows"] == 120
    # samples outside the traced window are not counted: with none
    # inside, the whole window's two ends are
    assert read(dict(obs, samples=[]), **spec["args"]) == pytest.approx(
        100 * least["seconds"] / 0.025)
    assert read(dict(obs, trace={"programs": {}}), **spec["args"]) is None
    bare = {k: v for k, v in sample(1.0, 1).items() if k == "uptime_s"}
    assert read(dict(obs, samples=[], stats0=bare, stats1=bare),
                **spec["args"]) is None


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """A temporary benchmark root gets a configuration that names
    `sdar_moe`, a fixed-length generation mix at toy size and a cell;
    the benchmark's own run serves it and the architecture's own check
    (22 prompt positions in two chunks and three blocks = 13 forwards of
    4, then 6 positions and five blocks = 23 forwards whose keys are
    nearly all a block step's) comes out correct."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks import run as bench_run
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    with open(os.path.join(b, "configs", "toy-sdar.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "blockgen-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 6,
                   "block": 3, "blocks": 64, "warmup_first_tokens": 3,
                   "prompt_len": {"dist": "lognormal", "median": 24,
                                  "sigma": 0.4, "min": 8, "max": 48},
                   "output_len": {"dist": "lognormal", "median": 11,
                                  "sigma": 0.3, "min": 5, "max": 18},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-sdar", "source": "none",
                            "file": "bm/configs/toy-sdar.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "sdar-toy", "config": "toy-sdar",
                              "traffic": "blockgen-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("sdar-toy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    lines = []
    out = bench_run.run_cell(reg, "sdar-toy", seed=2**31 + 59,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    # 22 tokens + 3 blocks (13 forwards), then 6 tokens + 5 blocks (23)
    assert check["finite"] and check["positions"] == 28 + 36 * B
    assert check["prefill_positions"] == 28 and check["block_forwards"] == 36
    assert check["procedure"] == "archs.sdar_moe.check_logits"
    assert check["programs"] == ["jit__prefill_chunk",
                                 "jit__paged_block_step"]
    assert check["max_abs_diff"] <= 1e-4 \
        and check["argmax_equal"] == check["positions"]
