"""A mix's `order_seed` (PR 46, after the driver's check refused
`sala-longdoc`'s spread): the orders inside the blocks come from the mix
file, so every `--seed` offers one schedule; the token ids still come
from `--seed`.  A mix without the key is ordered by `--seed` as before."""

import pytest

import toy
from benchmarks.lib import traffic
from benchmarks.lib.registry import Registry

REPO = toy.REPO
SEEDS = (1, 2**31 + 7, 2**31 + 36353)


def test_longdoc_offers_one_schedule_whatever_the_seed():
    mix = Registry(REPO).traffic("longdoc")
    n, blocks = mix["block"], mix["blocks"]
    runs = [traffic.schedule(mix, s, 45.0, blocks) for s in SEEDS]
    assert runs[0] == runs[1] == runs[2]
    # the schedule is the one `--seed <order_seed>` drew before the key
    old = {k: v for k, v in mix.items() if k != "order_seed"}
    assert traffic.schedule(old, mix["order_seed"], 45.0, blocks) == runs[0]
    # and still stratified: every block the same two multisets, in an
    # order of its own
    first = runs[0][:n]
    orders = set()
    for b in range(blocks):
        blk = runs[0][b * n:(b + 1) * n]
        for key in ("prompt_len", "max_new"):
            assert sorted(r[key] for r in blk) == \
                sorted(r[key] for r in first)
        orders.add(tuple(r["prompt_len"] for r in blk))
    assert len(orders) > blocks // 2


def test_the_seed_still_draws_the_tokens():
    a = traffic.prompt_tokens(SEEDS[0], 3, 64, 73448)
    b = traffic.prompt_tokens(SEEDS[1], 3, 64, 73448)
    assert a != b


@pytest.mark.parametrize("name", ["chat", "doc", "batch", "moe_decode",
                                  "moe_reason"])
def test_a_mix_without_the_key_is_ordered_by_the_seed(name):
    mix = Registry(REPO).traffic(name)
    assert "order_seed" not in mix
    blocks = 4
    a = traffic.schedule(mix, SEEDS[0], 45.0, blocks)
    b = traffic.schedule(mix, SEEDS[1], 45.0, blocks)
    assert traffic.totals(a) == traffic.totals(b)
    if len({r["prompt_len"] for r in a}) > 1:
        assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
