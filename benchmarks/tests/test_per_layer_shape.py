"""BENCHMARK.json's per-layer list since PR 46: one entry a (quantity,
end-to-end metric it moves) with the cells that read it under `workloads`,
where until then an entry was a (quantity, cell).  Held here on the CPU:
every reading a cell's traced run printed under its old name is an entry
of that cell still, read by the same reader with the same arguments; the
list's shape; and the seven seconds of a replica's start through the real
registry."""

import pytest

import toy
from benchmarks.lib.registry import Registry

REPO = toy.REPO

# The parent's entries, cell by cell (PR 44's BENCHMARK.json: 127 one-cell
# entries less the six retired, and `replica_start_s` in its six cells).
OLD = {
    "mistral7b-chat": """
        gen_late_p99_ms.chat handle_overhead_ms.chat queue_wait_p50_ms.chat
        ttft_p50_ms.chat ttft_p90_ms.chat itl_p50_ms.chat itl_p95_ms.chat
        itl_p99_ms.chat itl_p90_sub_ms.chat itl_p95_sub_ms.chat
        decode_tick_ms.chat prefill_chunk_ms.chat paged_tick_roofline.chat
        replica_start_s device_idle_share.chat hbm_in_use_gb.chat
        hbm_filled_gb.chat prefill_turn_share.chat loop_host_ms.chat
        sweep_stall_ms.chat sweep_gap_share.chat jit_compiles_in_window.chat
        jit_compile_ms.chat loop_device_wait_share.chat
        attn_gather_ratio.chat prefill_tokens_per_chunk.chat
        prefill_pad_share.chat demote_offthread_share.chat land_wait_ms.chat
    """,
    "mistral7b-doc": """
        handle_overhead_ms.doc queue_wait_p50_ms.doc prefill_chunk_ms.doc
        prefill_chunk_roofline.doc replica_start_s device_idle_share.doc
        hbm_in_use_gb.doc hbm_filled_gb.doc loop_host_ms.doc
        sweep_stall_ms.doc jit_compiles_in_window.doc jit_compile_ms.doc
        loop_device_wait_share.doc prefill_tokens_per_chunk.doc
        prefill_pad_share.doc demote_offthread_share.doc land_wait_ms.doc
    """,
    "internlm2-batch": """
        batch_occupancy.sat kv_pool_used_share.sat decode_tick_ms.sat
        paged_tick_roofline.sat replica_start_s device_idle_share.sat
        hbm_in_use_gb.sat hbm_filled_gb.sat loop_host_ms.sat
        sweep_stall_ms.sat jit_compiles_in_window.sat jit_compile_ms.sat
        loop_device_wait_share.sat attn_gather_ratio.sat
        prefill_tokens_per_chunk.sat prefill_pad_share.sat
        demote_offthread_share.sat land_wait_ms.sat
    """,
    "internlm2-train4": """
        train_step_mfu.train train_step_ms.train
        collective_exposed_share.train device_idle_share.train
        hbm_in_use_gb.train
    """,
    "sala-longdoc": """
        replica_start_s decode_tick_ms.long prefill_chunk_ms.long
        paged_tick_roofline.long prefill_chunk_roofline.long
        sparse_attended_share.long prefill_sparse_share.long
        prefill_turn_share.long batch_occupancy.long kv_pool_used_share.long
        loop_host_ms.long loop_device_wait_share.long device_idle_share.long
        hbm_in_use_gb.long hbm_filled_gb.long jit_compiles_in_window.long
        jit_compile_ms.long
    """,
    "dsv2-decode": """
        replica_start_s expert_local_share.moe experts_touched_share.moe
        expert_load_peak.moe decode_tick_ms.moe prefill_chunk_ms.moe
        paged_tick_roofline.moe prefill_chunk_roofline.moe
        batch_occupancy.moe kv_pool_used_share.moe prefill_turn_share.moe
        prefill_tokens_per_chunk.moe prefill_pad_share.moe
        attn_gather_ratio.moe loop_host_ms.moe loop_device_wait_share.moe
        device_idle_share.moe hbm_in_use_gb.moe hbm_filled_gb.moe
        jit_compiles_in_window.moe jit_compile_ms.moe
    """,
    "kexaone-reason": """
        replica_start_s decode_tick_ms.kx prefill_chunk_ms.kx
        paged_tick_roofline.kx prefill_chunk_roofline.kx
        expert_local_share.kx experts_touched_share.kx expert_load_peak.kx
        batch_occupancy.kx kv_pool_used_share.kx prefill_turn_share.kx
        prefill_tokens_per_chunk.kx attn_gather_ratio.kx loop_host_ms.kx
        loop_device_wait_share.kx device_idle_share.kx hbm_in_use_gb.kx
        hbm_filled_gb.kx jit_compiles_in_window.kx kv_held_share.kx
    """,
}
# An entry that moved `out_tok_per_s` under a cell's tag is `.tput` now,
# but for the one name a tier-1 test of the program's pins
# (tests/test_exaone_moe.py: `kv_held_share.kx`).
MERGED_TAGS = ("sat", "long", "moe", "kx")
KEPT = ("kv_held_share.kx",)
RETIRED = ("compiles_in_window.chat", "compiles_in_window.sat",
           "compiles_in_window.long", "compiles_in_window.doc",
           "compiles_in_window.train", "tier_stall_ms.chat")
STARTS = ("spawn", "boot", "load", "build", "warm", "unaccounted",
          "backend_init")
SERVING = ("mistral7b-chat", "internlm2-batch", "mistral7b-doc",
           "sala-longdoc", "dsv2-decode", "kexaone-reason")
PAIRS = [(old, cell) for cell, names in OLD.items()
         for old in names.split()]


def new_name(old: str) -> str:
    base, _, tag = old.rpartition(".")
    if old not in KEPT and tag in MERGED_TAGS:
        return base + ".tput"
    return old


@pytest.fixture(scope="module")
def reg():
    return Registry(REPO)


def test_the_table_is_the_parents_list():
    assert len(PAIRS) == len(set(PAIRS)) == 127
    assert sum(old == "replica_start_s" for old, _ in PAIRS) == 6


@pytest.mark.parametrize("old,cell", PAIRS,
                         ids=["%s-%s" % p for p in PAIRS])
def test_an_old_reading_is_an_entry_of_its_cell_still(reg, old, cell):
    new = new_name(old)
    mine = {m["name"]: m for m in reg.metrics_for(cell, "per_layer")}
    assert new in mine and cell in mine[new]["workloads"]
    assert old == new or old not in mine
    assert reg.metric(new) == reg.metric(old)      # reader and args


def test_one_entry_a_quantity_and_the_metric_it_moves(reg):
    spec = reg.spec
    cells = [w["name"] for w in spec["workloads"]]
    entries = spec["per_layer"]
    assert len(entries) <= 96
    names = [m["name"] for m in entries]
    assert len(names) == len(set(names))
    keys = [(m["name"].rsplit(".", 1)[0], m["moves"]) for m in entries]
    assert len(keys) == len(set(keys))
    for m in entries:
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        assert len(m["workloads"]) == len(set(m["workloads"]))
        tag = m["name"].rpartition(".")[2]
        assert (tag == "tput") == (m["moves"] == "out_tok_per_s"
                                   and m["name"] not in KEPT), m["name"]
    assert not set(RETIRED) & set(names)
    for name in RETIRED:              # their files stay: nothing is edited
        assert set(reg.metric(name)) == {"reader", "args"}
    # every cell that reports `out_tok_per_s` is in some `.tput` list, and
    # a cell of the table still reads what its tagged entries were, plus
    # PR 38's two; cells and entries added since PR 46 are their PRs' own
    tput = {m["name"]: m["workloads"] for m in entries
            if m["name"].endswith(".tput")}
    on = next(m for m in spec["end_to_end"]
              if m["name"] == "out_tok_per_s")["workloads"]
    assert {c for ws in tput.values() for c in ws} == set(on)
    for cell in sorted({c for _, c in PAIRS} & set(on)):
        was = {new_name(o) for o, c in PAIRS
               if c == cell and new_name(o).endswith(".tput")}
        if cell == "kexaone-reason":
            was |= {"prefill_pad_share.tput", "jit_compile_ms.tput"}
        assert was <= {n for n, ws in tput.items() if cell in ws}, cell


def test_a_starts_seconds_read_through_the_real_registry(reg):
    start = {p: 1.5 + i for i, p in enumerate(STARTS)}
    start.update(trace_id="t", how="zygote", unpickle=0.12)
    obs = {"replica_info": {"start": start}}
    for cell in SERVING:
        got = {}
        for m in reg.metrics_for(cell, "per_layer"):
            if m["name"].startswith("start_"):
                spec = reg.metric(m["name"])
                got[m["name"]] = reg.reader(spec["reader"])(
                    obs, **spec["args"])
        assert got == {f"start_{p}_s": start[p] for p in STARTS}
    by_name = {m["name"]: m for m in reg.spec["per_layer"]}
    for p in STARTS:
        m = by_name[f"start_{p}_s"]
        assert m["workloads"] == by_name["replica_start_s"]["workloads"]
        assert (m["moves"], m["layer"], m["source"]) == (
            "setup_s", by_name["replica_start_s"]["layer"], "program_span")
        spec = reg.metric(m["name"])
        read = reg.reader(spec["reader"])
        # a parent commit's replica says nothing of its start
        assert read({"replica_info": {"model": "m"}}, **spec["args"]) is None
        assert read({}, **spec["args"]) is None
    assert not [m for m in reg.metrics_for("internlm2-train4", "per_layer")
                if m["name"].startswith("start_")]

