"""A toy benchmark root in a temporary directory: the real BENCHMARK.json's
cells and metrics over a 2-layer, 64-wide configuration and mixes a CPU
finishes in seconds.  Nothing of the real benchmark is edited to make
it: it is all new files, found by name."""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

CONFIG = {
    "name": "toy", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
    "torch_dtype": "float32",
    "serving": {"engine": {"num_slots": 4, "max_seq": 128, "page_size": 8,
                           "kv_pages": 64},
                "check": {"prompt_len": 20, "decode_tokens": 4,
                          "tolerance": {"max_abs_diff": 1e-3,
                                        "mean_abs_diff": 1e-4}}}}

MIXES = {
    "chat": {"kind": "serve", "loop": "open", "rate_rps": 6.0,
             "blocks_per_window": 3, "warmup_blocks": 1,
             "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                            "min": 8, "max": 64},
             "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.4,
                            "min": 4, "max": 24}, "trace_seconds": 1},
    "batch": {"kind": "serve", "loop": "closed", "clients": 8, "block": 8,
              "blocks": 64, "warmup_first_tokens": 4,
              "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.3,
                             "min": 8, "max": 32},
              "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.3,
                             "min": 8, "max": 32}, "trace_seconds": 1},
    "doc": {"kind": "serve", "loop": "closed", "clients": 1, "block": 4,
            "blocks": 64, "warmup_first_tokens": 2,
            "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.2,
                           "min": 32, "max": 64},
            "output_len": {"dist": "fixed", "value": 4}, "trace_seconds": 1},
    "train4k": {"kind": "train", "mesh": {"fsdp": 2, "tp": 2}, "batch": 8,
                "seq": 32, "optimizer": "adamw", "learning_rate": 3e-4,
                "mu_dtype": "float32", "remat": True, "warmup_steps": 2,
                "trace_steps": 2},
}


GPT_CONFIG = dict(CONFIG, name="toy-gpt", arch="gpt", num_key_value_heads=4)


def add_gpt(root: str) -> str:
    """Into a root that `build` made, a second architecture as a later
    PR would bring one: `archs/gpt/` (copied from rehearsal/), a
    configuration that names it, one chat-mix cell and its entries.
    Only new files and appended entries."""
    b = os.path.join(root, "bm")
    shutil.copytree(os.path.join(HERE, "rehearsal", "archs"),
                    os.path.join(b, "archs"))
    with open(os.path.join(b, "configs", "toy-gpt.json"), "w") as f:
        json.dump(GPT_CONFIG, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-gpt", "source": "none",
                            "file": "bm/configs/toy-gpt.json",
                            "reduced": [], "why": "a second architecture"})
    spec["workloads"] += [
        {"name": "gpt-chat", "config": "toy-gpt", "traffic": "chat",
         "chips": 1, "why": "the GPT block under the chat mix"},
        {"name": "gpt-train", "config": "toy-gpt", "traffic": "train4k",
         "chips": 4, "why": "refused: the architecture only serves"}]
    judged = {"itl_p90_ms": "gpt-chat", "train_tok_per_s": "gpt-train"}
    for m in spec["end_to_end"]:
        if m["name"] in judged:
            m["workloads"].append(judged[m["name"]])
    for base, unit in (("paged_tick_roofline", "%"),
                       ("prefill_chunk_roofline", "%"),
                       ("hbm_filled_gb", "GB")):   # no metric file of its own
        spec["per_layer"].append({
            "name": base + ".gpt", "unit": unit, "better": "higher",
            "source": "device_trace", "layer": "Kernels",
            "moves": "itl_p90_ms", "workloads": ["gpt-chat"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def add_checked(root: str, arch: str = "gpt_verify",
                source: str = None) -> str:
    """Into a root that `add_gpt` made, an architecture that brings its
    own logits check: `archs/gpt_verify.py` came with rehearsal/; any
    other `arch` is a flat module written here from `source`.  One
    configuration `toy-<arch>` and one chat-mix cell `<arch>-chat`:
    new files and appended entries."""
    b = os.path.join(root, "bm")
    if source is not None:
        with open(os.path.join(b, "archs", arch + ".py"), "w") as f:
            f.write(source)
    with open(os.path.join(b, "configs", f"toy-{arch}.json"), "w") as f:
        json.dump(dict(GPT_CONFIG, name=f"toy-{arch}", arch=arch), f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": f"toy-{arch}", "source": "none",
                            "file": f"bm/configs/toy-{arch}.json",
                            "reduced": [], "why": "its own logits check"})
    spec["workloads"].append(
        {"name": f"{arch}-chat", "config": f"toy-{arch}", "traffic": "chat",
         "chips": 1, "why": "the GPT block, checked by its own procedure"})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p90_ms":
            m["workloads"].append(f"{arch}-chat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def build(root: str) -> str:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    b = os.path.join(root, "bm")
    for d in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(b, d), exist_ok=True)
    with open(os.path.join(b, "configs", "toy.json"), "w") as f:
        json.dump(CONFIG, f)
    for name, mix in MIXES.items():
        with open(os.path.join(b, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(b, "metrics"), dirs_exist_ok=True)
    spec = dict(real, paths=["bm"], configs=[
        {"name": "toy", "source": "none", "file": "bm/configs/toy.json",
         "reduced": [], "why": "toy"}])
    spec["workloads"] = [dict(w, config="toy") for w in real["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
