"""GLM-5 (`glm_moe_dsa`) at toy widths on the CPU, seeded weights whose
indexer, rotation and router bias all move the logits: the engine's own
two programs (chunks, then ticks that choose keys a token out of the
paged pool) against one forward of the plain reference, at sizes that
keep every ratio (five layers, one dense, `index_topk` 8 under contexts
below, at and past it, 16 experts top-4 through a bias with 4 held,
pages of 4), the chosen sets against the reference's, the layer against
deepseek_v2's below `index_topk`, rows whose pages interleave in the
pool, a slot that changes hands, a tick on either side of the depth
where walking a row's pages under the choice stops being cheaper than
gathering the chosen, the controls a comparison must catch,
the share test, the choice without a sort against `lax.top_k`, the
yardstick against the program's shapes at the configuration's sizes, the
refusals by declaration, and the toy configuration served to
`correct`."""

import ast
import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode
from ray_tpu.models import glm_moe_dsa as gm
from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

L, E_ALL, HELD, D = 5, 16, 4, 64
TOPK, K = 8, 4
C = {
    "name": "toy-glm5", "arch": "glm_moe_dsa", "attention_bias": False,
    "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
    "head_dim": 8, "hidden_size": D, "index_head_dim": 16,
    "index_n_heads": 4, "index_topk": TOPK,
    "indexer_rope_interleave": True, "intermediate_size": 96,
    "kv_lora_rank": 32, "max_position_embeddings": 4096,
    "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": HELD,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": K,
    "num_hidden_layers": L, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 0, "q_lora_rank": 48, "qk_head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 128,
    "expert_offset": 0, "torch_dtype": "float32",
    "published": {"num_hidden_layers": 10, "n_routed_experts": E_ALL,
                  "vocab_size": 1024},
    "serving": {"engine": {"num_slots": 3, "max_seq": 256, "page_size": 4,
                           "kv_pages": 192, "prefill_chunk": 16,
                           "enable_prefix_cache": False},
                "check": {"prompt_len": 64, "decode_tokens": 6,
                          "tolerance": {"max_abs_diff": 2e-4,
                                        "mean_abs_diff": 2e-5}}}}
ROWS = 3
N_MOE = L - 1


@pytest.fixture(scope="module")
def ref_mod():
    spec = importlib.util.spec_from_file_location(
        "glm5_reference", os.path.join(BENCH, "archs", "glm_moe_dsa",
                                       "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arch():
    from benchmarks.lib.registry import arch_of
    return arch_of(C, BENCH)


@pytest.fixture(scope="module")
def model(arch):
    """The seeded weights as they are drawn, but for the norms' gains,
    which are bumped so a missing one shows."""
    cfg = arch.build(C, C["serving"]["engine"]["max_seq"], remat=False)
    params = arch.init(cfg, jax.random.PRNGKey(7), jnp.float32)
    bump = iter(jax.random.split(jax.random.PRNGKey(8), 64))

    def bumped(path, w):
        if path[-1].key in ("ln1", "ln2", "ln_f", "kv_norm", "q_norm",
                            "ik_norm"):
            return w + 0.1 * jax.random.normal(next(bump), w.shape)
        return w
    return cfg, jax.tree_util.tree_map_with_path(bumped, params)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, C["vocab_size"], size=n).astype(np.int32)


class Driver:
    """The engine's two jitted programs over one cache, driven by hand
    as the engine's admission and tick do."""

    def __init__(self, cfg, params, psz, chunk, pages=192, nblk=None,
                 rows=ROWS):
        self.cfg, self.params, self.psz, self.chunk = cfg, params, psz, chunk
        self.cache = decode.paged_body(cfg).init_paged_cache(
            cfg, pages + 1, psz, rows)
        self.bt = np.zeros((rows, nblk or 256 // psz), np.int32)
        self.pos = np.zeros((rows,), np.int32)
        self.tok = np.zeros((rows,), np.int32)
        self.next_page = 1

    def pages(self, n, stride=1):
        """n fresh pages, every `stride`-th of the pool's."""
        got = self.next_page + stride * np.arange(n)
        self.next_page = int(got[-1]) + 1 if stride == 1 else self.next_page
        return got

    def admit(self, slot, toks, total, pages=None):
        n = -(-total // self.psz)
        row = np.zeros((self.bt.shape[1],), np.int32)
        row[:n] = self.pages(n) if pages is None else pages[:n]
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
        self.bt[slot], self.pos[slot] = row, len(toks)
        return np.concatenate(rows)

    def leave(self, slot):
        self.bt[slot], self.pos[slot], self.tok[slot] = 0, 0, 0

    def tick(self, feed):
        for slot, t in feed.items():
            self.tok[slot] = t
        _, logits, self.cache = engine_mod._paged_tick(
            self.params, jnp.asarray(self.tok), jnp.asarray(self.pos),
            self.cache, jnp.asarray(self.bt), self.cfg, with_logits=True)
        logits = np.asarray(logits)
        assert np.isfinite(logits).all()       # idle rows too
        for slot in feed:
            self.pos[slot] += 1
        return logits


def _one_sequence(drv, slot, toks, n_prompt, pages=None):
    rows = [drv.admit(slot, toks[:n_prompt], len(toks), pages)]
    for t in toks[n_prompt:]:
        rows.append(drv.tick({slot: t})[slot][None])
    return np.concatenate(rows)


# ------------------------------------ the engine's programs = one forward

CASES = {
    # page, chunk, prompt, ticks
    "chunk-16-padded-last": (4, 16, 75, 10),    # four whole chunks + 11 / 16
    "chunk-32": (4, 32, 100, 6),
    "chunk-128-one-padded": (8, 128, 40, 4),
    "whole-chunks": (4, 16, 64, 6),
    "pages-of-16": (16, 32, 90, 5),     # a position's page by comparison
    "prompt-under-topk": (4, 16, 5, 2),     # every query sees fewer keys
                                            # than it keeps
    "prompt-at-topk": (4, 16, 8, 4),        # the last prompt token keeps
                                            # all 8; the ticks refuse some
    "ticks-pass-topk": (4, 16, 3, 20),      # one padded chunk
    "one-token": (4, 16, 1, 12),
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_chunks_then_ticks_are_one_reference_forward(
        model, arch, case):
    """Every position's logits against the reference's full forward,
    with contexts below, at and past `index_topk` 8: a chunk attends
    under the reference's choice and a tick gathers the reference's keys
    alone, and the program's counters are the reference's routing and
    its choices."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES[case]
    drv = Driver(cfg, params, psz, chunk)
    toks = _tokens(n_prompt + n_decode, seed=len(case))
    got = _one_sequence(drv, 1, toks, n_prompt)
    want, routes, masks = arch.reference(params, jnp.asarray(toks), C,
                                         with_routes=True)
    assert np.asarray(want).std() > 0.05
    np.testing.assert_allclose(got, np.asarray(want), atol=5e-5)
    counts = gm.read_counters(drv.cache, cfg)
    routes, masks = np.asarray(routes), np.asarray(masks)
    n = len(toks)
    assert counts["pairs_routed"] == routes.size == n * K * N_MOE
    assert counts["pairs_local"] == int((routes < HELD).sum())
    # the reference's chosen sets: min(t + 1, topk) keys a position
    per = np.minimum(np.arange(n) + 1, TOPK)
    assert (masks.sum(-1) == per[None]).all()
    assert counts["dsa_keys_chosen"] == int(masks.sum())
    assert counts["dsa_tick_keys_chosen"] == int(masks[:, n_prompt:].sum())
    # a tick gathers and weighs the chosen latents and nothing else
    assert counts["dsa_tick_keys_attended"] == counts["dsa_tick_keys_chosen"]
    assert counts["dsa_rows_live"] == n_decode * L
    assert counts["dsa_rows_selecting"] == L * sum(
        p + 1 > TOPK for p in range(n_prompt, n))
    assert counts["dsa_keys_scored"] >= int(
        (np.arange(n) + 1).sum()) * L


def _spy_on_the_choice(run):
    """The masks `chosen_keys` hands on while `run()` goes, in order."""
    seen = []
    real = gm.chosen_keys

    def spy(scores, topk):
        mask = real(scores, topk)
        jax.debug.callback(lambda m: seen.append(np.asarray(m)), mask)
        return mask
    gm.chosen_keys = spy
    engine_mod._prefill_chunk.clear_cache()
    engine_mod._paged_tick.clear_cache()
    try:
        run()
        jax.effects_barrier()
    finally:
        gm.chosen_keys = real
        engine_mod._prefill_chunk.clear_cache()
        engine_mod._paged_tick.clear_cache()
    return seen


def test_the_chosen_sets_are_the_references(model, arch):
    """The program's own choice, layer by layer: the masks a prefill's
    chunks and a tick's rows choose by, against the 0/1 masks of the
    reference: equal wherever the reference's gap at the cut is above
    rounding, ties included (every (position, layer) here but at most 1
    in 100)."""
    cfg, params = model
    drv = Driver(cfg, params, 4, 16)
    toks = _tokens(96 + 8, seed=5)
    _, _, masks = arch.reference(params, jnp.asarray(toks), C,
                                 with_routes=True)
    masks = np.asarray(masks)

    def run():
        drv.admit(1, toks[:96], len(toks))
        for t in toks[96:]:
            drv.tick({1: t})
    seen = _spy_on_the_choice(run)
    chunks = (96 // 16) * L
    assert len(seen) == chunks + 8 * L
    same = total = 0
    for n, mask in enumerate(seen[:chunks]):
        chunk, layer = divmod(n, L)
        for j in range(16):
            t = chunk * 16 + j
            same += set(np.flatnonzero(mask[j]).tolist()) \
                == set(np.flatnonzero(masks[layer, t]).tolist())
            total += 1
    for n, mask in enumerate(seen[chunks:]):
        tick, layer = divmod(n, L)
        assert not mask[[0, 2]].any()               # idle rows choose none
        same += set(np.flatnonzero(mask[1]).tolist()) \
            == set(np.flatnonzero(masks[layer, 96 + tick]).tolist())
        total += 1
    assert same >= 0.99 * total, (same, total)


def test_below_index_topk_the_layer_is_deepseek_v2s(model, monkeypatch):
    """With `index_topk` above every context the choice keeps every key,
    and the layer is deepseek_v2's latent attention on the same weights:
    the two programs with the selection in place equal the two programs
    with `deepseek_v2._attn_chunk` / `_attn_tick` called with no
    `chosen` at all."""
    cfg, params = model
    wide = dataclasses.replace(cfg, index_topk=256)
    toks = _tokens(60, seed=9)
    got = _one_sequence(Driver(wide, params, 4, 16), 1, toks, 50)
    sparse = _one_sequence(Driver(cfg, params, 4, 16), 1, toks, 50)

    def dense_chunk(lp, x, l, cache, bt, start, valid, dc, cfg):
        return gm._ds._attn_chunk(lp, x, l, cache, bt, start, cfg,
                                  project=gm._project) + (dc,)

    def dense_tick(lp, x, l, cache, bt, pos, dc, cfg):
        return gm._ds._attn_tick(lp, x, l, cache, bt, pos, cfg,
                                 project=gm._project) + (dc,)
    monkeypatch.setattr(gm, "_attn_chunk", dense_chunk)
    monkeypatch.setattr(gm, "_attn_tick", dense_tick)
    # (another config object: the jitted programs are traced anew)
    dense = dataclasses.replace(cfg, index_topk=257)
    want = _one_sequence(Driver(dense, params, 4, 16), 1, toks, 50)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # ... and with the real `index_topk` the selection moves the logits
    assert np.abs(sparse - want).max() > 1e-3


def test_rows_whose_pages_interleave_choose_and_attend_as_each_alone(
        model, arch):
    """Rows 0 and 2 hold alternate pages of the pool (row 0 the odd
    ones, row 2 the even ones, out of order) and decode at different
    depths in the same ticks, row 2 under `index_topk` while row 0 is
    past it, row 1 idle; row 2's sequence ends and a SHORTER one is
    admitted into its slot and its pages while row 0 goes on: each
    row's logits are the reference's of its sequence alone."""
    cfg, params = model
    drv = Driver(cfg, params, 4, 16)
    a, b, c2 = _tokens(70, seed=1), _tokens(30, seed=2), _tokens(21, seed=3)
    odd = 1 + 2 * np.arange(40)
    even = (2 + 2 * np.arange(40))[::-1].copy()
    got_a = [drv.admit(0, a[:40], len(a), odd)]
    got_b = [drv.admit(2, b[:5], len(b), even)]
    for i in range(13):
        out = drv.tick({0: a[40 + i], 2: b[5 + i]})
        got_a.append(out[0][None])
        got_b.append(out[2][None])
    counts = gm.read_counters(drv.cache, cfg)
    assert counts["dsa_rows_live"] == 2 * 13 * L
    # row 0 is past index_topk in every tick, row 2 from position 8 on
    assert counts["dsa_rows_selecting"] == (13 + 10) * L
    assert counts["dsa_tick_keys_attended"] == counts["dsa_tick_keys_chosen"]
    drv.leave(2)
    got_c = [drv.admit(2, c2[:5], len(c2), even)]
    for i in range(16):
        out = drv.tick({0: a[53 + i], 2: c2[5 + i]})
        got_a.append(out[0][None])
        got_c.append(out[2][None])
    for got, toks in ((got_a, a[:69]), (got_b, b[:18]), (got_c, c2)):
        want = arch.reference(params, jnp.asarray(toks), C)
        np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                                   atol=5e-5)


def test_a_tick_of_sixteen_rows_takes_its_live_rows_eight_a_trip(
        model, arch):
    """Sixteen rows, eleven of them live at unequal depths and scattered
    among idle ones: the indexer and the gather take the live rows first,
    eight a trip (one full block, one of three live rows and five idle),
    each row over its own pages as far as the deepest of its block; every
    live row's logits are the reference's of its sequence alone, idle
    rows are finite and count nothing."""
    cfg, params = model
    drv = Driver(cfg, params, 4, 16, pages=400, rows=16)
    live = [0, 2, 3, 5, 6, 7, 9, 11, 12, 14, 15]
    lens = [40, 3, 17, 9, 33, 8, 70, 12, 25, 7, 51]
    seqs = {r: _tokens(n + 5, seed=100 + r) for r, n in zip(live, lens)}
    got = {r: [drv.admit(r, seqs[r][:n], n + 5)]
           for r, n in zip(live, lens)}
    before = gm.read_counters(drv.cache, cfg)
    for i in range(5):
        out = drv.tick({r: seqs[r][n + i] for r, n in zip(live, lens)})
        for r in live:
            got[r].append(out[r][None])
    for r in live:
        want = arch.reference(params, jnp.asarray(seqs[r]), C)
        np.testing.assert_allclose(np.concatenate(got[r]),
                                   np.asarray(want), atol=5e-5)
    counts = gm.read_counters(drv.cache, cfg)
    gain = {k: counts[k] - before[k] for k in counts
            if k.startswith("dsa_")}
    assert gain["dsa_rows_live"] == 5 * len(live) * L
    chose = L * sum(min(n + i + 1, TOPK) for n in lens for i in range(5))
    assert gain["dsa_tick_keys_chosen"] == chose \
        == gain["dsa_tick_keys_attended"] == gain["dsa_keys_chosen"]
    assert gain["dsa_rows_selecting"] == L * sum(
        n + i + 1 > TOPK for n in lens for i in range(5))


# ---------------------------------- a tick walks or gathers, by the depth

@pytest.mark.parametrize("tick_attention", ["kernel"], indirect=True)
@pytest.mark.parametrize("side", ["under-the-crossing", "past-the-crossing"])
def test_a_tick_walks_or_gathers_by_its_rows_depth_and_both_are_one_tick(
        model, arch, tick_attention, monkeypatch, side):
    """The tick as a TPU traces it (conftest's `tick_attention`: the
    ragged kernel interpreted, in blocks of 8 keys so that a toy row
    walks several).  Two live rows and an idle one between them, at
    depths whose mean lies under the crossing of `deepseek_v2.walks` (a row's keys held x
    the walk's cost a key against `index_topk` x the gather's) or past
    it: the rule, read from the positions, takes the walk under it and
    the gather past it (`dsa_rows_walked` counts every live row and
    layer, or none), and the same ticks FORCED to the other fetch give
    the same logits, the same two pools and the same `dsa_*` counters
    but that one; the keys attention weighed are the keys chosen on
    both, counted by the kernel on the walk; and `attn_keys_gathered`
    says what the fetch taken copies: each live row's own blocks, or
    `index_topk` slots a live row."""
    cfg, params = model
    ds = gm._ds
    cross = TOPK * ds._GATHER_NS_A_KEY / ds._WALK_NS_A_KEY
    assert 16 < cross < 180, cross            # both sides fit the toy table
    walks = side == "under-the-crossing"
    lens = [int(cross * f) for f in ((0.35, 0.8) if walks else (1.15, 1.35))]
    ticks, psz, nblk = 3, 4, 256 // 4
    seqs = {r: _tokens(n + ticks, seed=50 + r) for r, n in zip((0, 2), lens)}
    token = gm._ds._lat_width(cfg) * jnp.dtype(cfg.dtype).itemsize

    def run():
        engine_mod._paged_tick.clear_cache()
        drv = Driver(cfg, params, psz, 16)
        for r, n in zip((0, 2), lens):
            drv.admit(r, seqs[r][:n], n + ticks)
        before = gm.read_counters(drv.cache, cfg)
        rows, copied = [], []
        for i in range(ticks):
            copied.append(gm.attn_keys_gathered(cfg, drv.pos, psz, nblk))
            assert bool(ds.walks(drv.pos, TOPK)) == bool(
                ds.walks(jnp.asarray(drv.pos), TOPK))
            rows.append(drv.tick({r: seqs[r][n + i]
                                  for r, n in zip((0, 2), lens)}))
        counts = gm.read_counters(drv.cache, cfg)
        gain = {k: counts[k] - before[k] for k in counts
                if k.startswith("dsa_")}
        return np.stack(rows), drv, gain, copied

    got, drv, gain, copied = run()
    assert gain["dsa_rows_walked"] == (2 * ticks * L if walks else 0)
    assert gain["dsa_rows_live"] == 2 * ticks * L
    assert gain["dsa_tick_keys_attended"] == gain["dsa_tick_keys_chosen"] \
        == 2 * ticks * L * TOPK
    for i, n in enumerate(copied):
        pos = np.asarray([lens[0] + i, 0, lens[1] + i])
        assert n == (pa.keys_copied(pos, psz, nblk, token) * L if walks
                     else 2 * TOPK * L)
    # the walk weighs the reference's keys
    for r in (0, 2):
        want = np.asarray(arch.reference(params, jnp.asarray(seqs[r]), C))
        np.testing.assert_allclose(got[:, r], want[-ticks:], atol=5e-5)
    # ... and forced to the other fetch, the ticks are the same ticks
    monkeypatch.setattr(
        ds, "_GATHER_NS_A_KEY" if walks else "_WALK_NS_A_KEY", 0.0)
    other, forced, gain2, copied2 = run()
    assert gain2.pop("dsa_rows_walked") == (0 if walks else 2 * ticks * L)
    del gain["dsa_rows_walked"]
    assert gain2 == gain
    assert copied2 != copied
    np.testing.assert_allclose(other[:, [0, 2]], got[:, [0, 2]], atol=2e-5)
    for name in ("lat", "idx"):
        np.testing.assert_allclose(np.asarray(forced.cache[name][:, 1:]),
                                   np.asarray(drv.cache[name][:, 1:]),
                                   atol=2e-5)


# ------------------------------------------------------------ the controls

CONTROLS = {"indexer dropped": {"_no_selection": True},
            "index_topk halved": {"_index_topk": TOPK // 2},
            "head weights 1": {"_head_weights_one": True},
            "ReLU dropped": {"_no_relu": True},
            "selection bias dropped": {"_no_router_bias": True},
            "routed_scaling_factor 1": {"_routed_scaling_factor": 1.0},
            "index_topk - 1": {"_index_topk": TOPK - 1},
            "top-3 for top-4": {"_top_k": 3},
            "indexer key's bias off": {"_no_index_bias": True},
            "indexer's RoPE off": {"_no_index_rope": True},
            "RoPE dropped": {"_no_rope": True}}


@pytest.fixture(scope="module")
def served_logits(model):
    cfg, params = model
    toks = _tokens(102, seed=11)
    return toks, _one_sequence(Driver(cfg, params, 4, 16), 0, toks, 96)


@pytest.mark.parametrize("control", list(CONTROLS) + ["float8 matmuls"])
def test_each_control_is_another_model(model, arch, ref_mod, served_logits,
                                       control):
    """The reference with one mechanism changed is far from the served
    logits, which sit on the unchanged reference: every mechanism is
    live under the seeded weights."""
    cfg, params = model
    toks, got = served_logits
    if control == "float8 matmuls":
        wrong = arch.reference(params, jnp.asarray(toks), C,
                               round_to="float8_e4m3fn")
    else:
        wrong = arch.reference(params, jnp.asarray(toks),
                               dict(C, **CONTROLS[control]))
    right = arch.reference(params, jnp.asarray(toks), C)
    assert np.abs(got - np.asarray(right)).max() < 5e-5
    assert np.abs(got - np.asarray(wrong)).max() > 1e-3, control
    assert all(key in ref_mod.SWITCHES
               for switches in CONTROLS.values() for key in switches)


# ------------------------------------------- the share, the choice by hand

def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer(
        model, ref_mod):
    """What each of the 4 shares of an expert layer computes of its
    routed part (4 held experts of 16, the router scoring all 16), added
    up with the shared expert once, is the layer with every expert held:
    in the reference, and in the program's `routed_experts` share by
    share."""
    cfg, params = model
    f32 = jnp.float32
    D_, F = C["hidden_size"], C["moe_intermediate_size"]
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    draw = lambda k, *s: 0.2 * jax.random.normal(k, s, f32)  # noqa: E731
    whole = {"w_gate": draw(ks[0], E_ALL, D_, F),
             "w_up": draw(ks[1], E_ALL, D_, F),
             "w_down": draw(ks[2], E_ALL, F, D_)}
    lp = dict(params["layers"][1], experts=whole)
    h = jax.random.normal(ks[3], (24, D_), f32)
    uncut = dict(C, n_routed_experts=E_ALL, expert_offset=0)
    want = ref_mod.moe(h, lp, uncut)
    share0 = dict(lp, experts=jax.tree_util.tree_map(lambda w: w[:HELD],
                                                     whole))
    parts = ref_mod.moe(h, share0, C) \
        - ref_mod.moe(h, share0, C, with_shared=False)      # shared alone
    ids, weights = gm._em.route(lp["router"], lp["router_bias"], h, cfg)
    live = jnp.ones((24,), bool)
    program = jnp.zeros_like(want)
    for share in range(E_ALL // HELD):
        held = jax.tree_util.tree_map(
            lambda w: w[share * HELD:(share + 1) * HELD], whole)
        parts = parts + ref_mod.moe(
            h, dict(lp, experts=held), dict(C, expert_offset=share * HELD),
            with_shared=False)
        routed, sizes = gm._ds.routed_experts(
            held, h, ids, weights, live,
            dataclasses.replace(cfg, expert_offset=share * HELD))
        program = program + routed
    np.testing.assert_allclose(np.asarray(parts), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(program),
        np.asarray(ref_mod.moe(h, lp, uncut, with_shared=False)), atol=2e-4)


@pytest.mark.parametrize("N,S,k,most", [
    (64, 1024, 100, 1024), (64, 1024, 100, 128), (64, 1024, 100, 300),
    (128, 512, 512, 512), (3, 256, 24, 130), (64, 2048, 300, 1000),
    (5, 40, 7, 9)])
def test_the_choice_without_a_sort_is_top_k(N, S, k, most):
    """`chosen_keys` (a threshold by bisection) and its listing by
    running counts, over the narrowest width that holds every visible
    key, name `lax.top_k`'s set, ties by the lower position, for queries
    that see nothing, fewer than k, exactly k and up to `most` keys,
    with scores rounded so that ties abound."""
    rng = np.random.default_rng(N + S)
    x = np.round(rng.normal(size=(N, S)), 1)
    vis = rng.integers(0, most + 1, size=N)
    vis[:3] = 0, min(k, most) - 1, min(k, most)
    x = np.where(np.arange(S)[None] < vis[:, None], x, -np.inf
                 ).astype(np.float32)
    kk = min(k, S)

    def choose(x, need):
        mask = gm._narrowest(
            x, need, kk, lambda s: jnp.pad(gm.chosen_keys(s, k),
                                           ((0, 0), (0, S - s.shape[1]))))
        idx, ok = gm._narrowest(
            x, need, kk,
            lambda s: gm._listed_by_blocks(gm.chosen_keys(s, k), kk))
        return mask, idx, ok
    mask, idx, ok = jax.jit(choose)(jnp.asarray(x), jnp.int32(vis.max()))
    best, ref = jax.lax.top_k(jnp.asarray(x), kk)
    mask, idx, ok, ref, refok = map(np.asarray, (mask, idx, ok, ref,
                                                 jnp.isfinite(best)))
    for n in range(N):
        want = sorted(ref[n][refok[n]].tolist())
        assert sorted(idx[n][ok[n]].tolist()) == want, n
        assert np.flatnonzero(mask[n]).tolist() == want, n
        assert (np.diff(idx[n][ok[n]]) > 0).all()
        assert ok[n].sum() == min(vis[n], k)


# ------------------------------------- the benchmark's side of the model

def _real_config():
    with open(os.path.join(BENCH, "configs", "glm-5-ep16-d5.json")) as f:
        return json.load(f)


def test_the_yardstick_counts_what_the_program_holds(arch):
    """`costs.weight_bytes` and `kv_bytes_per_token` against the
    program's own shapes at the configuration's sizes (no array is
    made), ISSUE 66's arithmetic, and what a tick's selection needs:
    2,048 latent rows a row, not the context."""
    c = _real_config()
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    assert (cfg.first_k_dense, cfg.n_moe, cfg.experts_held) == (1, 4, 16)
    assert (cfg.n_routed_experts, cfg.top_k, cfg.index_topk,
            cfg.index_n_heads) == (256, 8, 2048, 32)
    params = jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype))
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))
    assert arch.weight_bytes(c) == held == 7_832_013_824
    assert arch.total_params(c) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert arch.mixer_params(c)["bf16"] == 174_391_296
    assert arch.expert_params(c) == 37_748_736
    cache = jax.eval_shape(lambda: decode.paged_body(cfg).init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"]))
    assert cache["lat"].shape == (5, e["kv_pages"] + 1, 64, 640)
    assert cache["idx"].shape == (5, e["kv_pages"] + 1, 64, 128)
    assert arch.kv_bytes_per_token(c) == 7680
    assert arch.kv_bytes_per_token(c) * 64 * (e["kv_pages"] + 1) \
        == (cache["lat"].size + cache["idx"].size) * 2
    rows, ctx = 48, 48 * 9800
    attend = arch.dsa_attend(c, rows, rows * 2048)
    assert attend["bytes"] < 5 * 2 * rows * 2048 * 1280 * 1.1
    assert arch.dsa_index(c, rows, ctx, ctx)["bytes"] > 5 * ctx * 256
    tick = arch.decode_tick(c, rows, ctx)
    assert 5.5e9 < tick["bytes"] < 8.5e9        # the weights lead
    assert arch.decode_tick(c, rows, 4 * ctx)["bytes"] \
        < tick["bytes"] + 5 * 3 * ctx * (256 + 8) * 1.01   # idx, not lat
    chunk = arch.prefill_chunk(c, 512, 8192, False)
    assert 2.5e12 < chunk["flops"] < 5e12


def test_the_configuration_file_holds_the_catalogs_numbers():
    c = _real_config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r.get("name") == "GLM-5")
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"]) == sorted(c["published"]) \
        == sorted(["num_hidden_layers", "first_k_dense_replace",
                   "n_routed_experts", "vocab_size",
                   "num_nextn_predict_layers"])
    assert all(c["published"][k] == row["config"][k] for k in differs)
    assert "16" in c["stands_for"] and "Hadamard" in json.dumps(c["assumed"]) \
        and "float8" in json.dumps(c["assumed"])
    for key in ("reduced_why", "stands_for", "resident_bytes", "assumed",
                "departures"):
        assert c[key], key


def test_the_reference_imports_jax_alone():
    path = os.path.join(BENCH, "archs", "glm_moe_dsa", "reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"jax", "__future__", "math"}, names


def test_the_new_cells_files_load_through_the_registry():
    from benchmarks.lib import traffic
    from benchmarks.lib.registry import Registry
    reg = Registry(REPO)
    cell = reg.cell("glm5-longctx")
    assert cell["config"] == "glm-5-ep16-d5" and cell["chips"] == 1
    mix = reg.traffic(cell["traffic"])
    engine = reg.config(cell["config"])["serving"]["engine"]
    rows = engine["num_slots"]
    assert mix["clients"] == 2 * rows and mix["block"] == rows \
        == mix["warmup_first_tokens"]
    plan = traffic.schedule(mix, 5, 45.0, 2)
    lens = sorted(r["prompt_len"] for r in plan[:rows])
    assert lens[0] >= 4096 and lens[-1] <= 32768 and len(plan) == 2 * rows
    assert max(r["prompt_len"] + r["max_new"] for r in plan) \
        <= engine["max_seq"]
    assert plan == traffic.schedule(mix, 6, 45.0, 2)     # one order
    # every row is past index_topk from its first tick
    assert min(r["prompt_len"] for r in plan) > 2048
    names = {m["name"] for m in reg.metrics_for("glm5-longctx",
                                                "per_layer")}
    dsv2 = {m["name"] for m in reg.metrics_for("dsv2-decode", "per_layer")}
    new = {"dsa_attended_ratio.tput", "dsa_scored_per_chosen.tput",
           "dsa_rows_selecting_share.tput", "dsa_rows_walked_share.tput"}
    assert names == dsv2 | new and not dsv2 & new
    obs = {"stats0": {"dsa_tick_keys_attended": 10,
                      "dsa_tick_keys_chosen": 10, "dsa_keys_chosen": 50,
                      "dsa_keys_scored": 100, "dsa_rows_selecting": 1,
                      "dsa_rows_live": 2, "dsa_rows_walked": 2},
           "stats1": {"dsa_tick_keys_attended": 10 + 2048 * 19,
                      "dsa_tick_keys_chosen": 10 + 2048 * 19,
                      "dsa_keys_chosen": 50 + 2048 * 38,
                      "dsa_keys_scored": 100 + 38 * 20480,
                      "dsa_rows_selecting": 20, "dsa_rows_live": 21,
                      "dsa_rows_walked": 21}}
    for name, want in (("dsa_attended_ratio.tput", 1.0),
                       ("dsa_scored_per_chosen.tput", 10.0),
                       ("dsa_rows_selecting_share.tput", 100.0),
                       ("dsa_rows_walked_share.tput", 100.0)):
        spec = reg.metric(name)
        assert reg.reader(spec["reader"])(obs, **spec["args"]) \
            == pytest.approx(want)
        # a program without the counters (the parent commit): nothing
        assert reg.reader(spec["reader"])(
            {"stats0": {}, "stats1": {}}, **spec["args"]) is None


# ------------------------------------------------------------- guards

KW = dict(num_slots=ROWS, page_size=4, prefill_chunk=16, kv_pages=192)


@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, enable_prefix_cache=False, **KW)
    yield eng
    eng.stop()


@pytest.mark.parametrize("what", [
    "kv_tiering", "kv_export", "kv_import", "session", "migrate_local",
    "speculation", "prefill_chunk_pages"])
def test_what_cannot_frame_a_latent_page_refuses_by_declaration(
        model, served, what):
    """A body with two pools under one table and no row state: whatever
    frames pages (tiers, kv_export / kv_import, sessions, migration)
    refuses it by what it declares (`framed` false), as it refuses
    deepseek_v2, naming the config."""
    cfg, params = model
    body = decode.paged_body(cfg)
    assert body.page_keys == ("lat", "idx") and not body.row_state_keys \
        and not body.framed and not body.has_row_state
    kw = dict(KW, enable_prefix_cache=False)
    latent = "latent page.*GlmMoeDsaConfig|GlmMoeDsaConfig.*latent page"
    if what == "kv_tiering":
        with pytest.raises(NotImplementedError, match=latent):
            GenerationEngine(params, cfg, kv_tiering=True, **kw)
    elif what == "kv_export":
        with pytest.raises(NotImplementedError, match=latent):
            served.kv_export([1, 2, 3])
    elif what == "kv_import":
        with pytest.raises(NotImplementedError, match=latent):
            served.kv_import([1, 2, 3], np.zeros(1), np.zeros(1))
    elif what == "session":
        with pytest.raises(NotImplementedError, match=latent):
            served.submit([1, 2, 3], max_new_tokens=2, session_id="s")
    elif what == "migrate_local":
        with pytest.raises(NotImplementedError, match=latent):
            kv_transfer.migrate_local(served, served, [1, 2, 3])
    elif what == "speculation":
        with pytest.raises(NotImplementedError, match="speculative"):
            GenerationEngine(params, cfg, speculate_k=2, **kw)
    else:
        with pytest.raises(ValueError, match="whole latent pages"):
            GenerationEngine(params, cfg, **dict(kw, prefill_chunk=18))


def test_the_prefix_cache_serves_two_pools_under_one_table(model, arch):
    """The radix cache shares whole pages by their index in the one
    block table, so the latent and the indexer's key of a shared token
    are shared together: a second request that repeats the first one's
    32-token prefix reads both from the first one's pages and yields the
    tokens an engine with no prefix cache yields."""
    cfg, params = model
    head = _tokens(32, seed=21).tolist()
    prompts = [head + _tokens(9, seed=22).tolist(),
               head + _tokens(14, seed=23).tolist()]
    outs = {}
    for cached in (False, True):
        eng = GenerationEngine(params, cfg, enable_prefix_cache=cached, **KW)
        try:
            outs[cached] = [eng.submit(p, max_new_tokens=8).result(
                timeout=300) for p in prompts]
            hits = eng.stats().to_dict()["prefix_hit_tokens"]
        finally:
            eng.stop()
    assert outs[True] == outs[False]
    assert hits >= 32
    seq = jnp.asarray(prompts[1] + outs[True][1][:7], jnp.int32)
    logits = np.asarray(arch.reference(params, seq, C))
    assert outs[True][1] == logits[len(prompts[1]) - 1:].argmax(-1).tolist()


def test_the_engine_serves_it_and_counts(model, served, arch):
    """Five requests on three rows (slots change hands): greedy tokens
    equal the reference's argmax chain, and the engine's counters add up
    on the known schedule: the reference's routing and choices, keys
    READ that stop at `index_topk` while keys HELD grow."""
    cfg, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in (41, 5, 70, 17, 33)]
    outs = [s.result(timeout=300) for s in
            [served.submit(p, max_new_tokens=9) for p in prompts]]
    local = chosen = tick_chosen = read = held = selecting = 0
    for p, out in zip(prompts, outs):
        seq = jnp.asarray(list(p) + out[:8], jnp.int32)
        logits, routes, masks = arch.reference(params, seq, C,
                                               with_routes=True)
        assert out == np.asarray(logits)[len(p) - 1:].argmax(-1).tolist()
        local += int((np.asarray(routes) < HELD).sum())
        chosen += int(np.asarray(masks).sum())
        tick_chosen += int(np.asarray(masks)[:, len(p):].sum())
        for pos in range(len(p), len(p) + 8):      # the ticks' positions
            read += min(pos + 1, TOPK) * L
            held += (pos + 1) * L
            selecting += (pos + 1 > TOPK) * L
    gain = {k: v - before[k] for k, v in served.stats().to_dict().items()
            if isinstance(v, (int, float))}
    ran = sum(len(p) + 8 for p in prompts)
    assert gain["moe_pairs_routed"] == ran * K * N_MOE
    assert gain["moe_pairs_local"] == local
    assert gain["dsa_keys_chosen"] == chosen
    assert gain["dsa_tick_keys_chosen"] == tick_chosen \
        == gain["dsa_tick_keys_attended"]
    assert gain["dsa_rows_live"] == 5 * 8 * L
    assert gain["dsa_rows_selecting"] == selecting
    assert gain["dsa_keys_scored"] > gain["dsa_keys_chosen"]
    assert gain["attn_keys_attended"] == read < held \
        == gain["attn_keys_resident"]
    assert gain["attn_keys_gathered"] == 5 * 8 * TOPK * L
    assert gain["prefill_tokens_sparse"] == sum(
        min(16, len(p) - s) for p in prompts
        for s in range(0, len(p), 16) if s >= TOPK)
    assert served.stats().row_state_bytes == 0


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """A temporary benchmark root gets a configuration that names
    `glm_moe_dsa`, a mix at toy size and a cell; the benchmark's own run
    serves it and its check (64 + 6 positions: four whole chunks, six
    ticks, `index_topk` 8 under the context) comes out correct."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks import run as bench_run
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    with open(os.path.join(b, "configs", "toy-glm5.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "longctx-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 6,
                   "block": 3, "blocks": 64, "warmup_first_tokens": 3,
                   "prompt_len": {"dist": "lognormal", "median": 60,
                                  "sigma": 0.5, "min": 30, "max": 200},
                   "output_len": {"dist": "fixed", "value": 10},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-glm5", "source": "none",
                            "file": "bm/configs/toy-glm5.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "glm5-toy", "config": "toy-glm5",
                              "traffic": "longctx-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("glm5-toy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    lines = []
    out = bench_run.run_cell(reg, "glm5-toy", seed=2**31 + 66,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 70
    assert check["max_abs_diff"] <= 2e-4 and check["argmax_equal"] == 70
