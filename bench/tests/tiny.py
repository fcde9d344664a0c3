"""Tiny configurations and mixes for the CPU tests: the same code paths
as the cells, at widths the CPU runs in seconds."""

QWEN3 = {
    "name": "qwen3-tiny", "arch": "qwen3-8b", "reference": "qwen_dense",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "attention_bias": False, "qk_norm": True,
    "launcher": ["--chip", "--arch", "qwen3-8b", "--kernel"],
    "check": {"served_logit_gap": 0.05},
}

QWEN15 = dict(QWEN3, name="qwen1.5-tiny", arch="qwen1.5-32b",
              num_attention_heads=6, num_key_value_heads=2,
              attention_bias=True, qk_norm=False,
              launcher=["--chip", "--arch", "qwen1.5-32b", "--kernel"])

CLOSED = {
    "loop": "closed", "clients": 4, "requests_per_client": 30,
    "layout_seed": 0, "shared_prefix": 0,
    "prompt": {"median": 32, "sigma": 0.5, "min": 16, "max": 64},
    "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 40},
    "launcher": ["--slots", "4", "--cache-len", "112", "--chunk", "32",
                 "--page-size", "16", "--num-blocks", "0",
                 "--hold-pages", "0"],
    "check_requests": 8,
}

OPEN = {
    "loop": "open", "rate_per_s": 4.0, "preroll_s": 1, "layout_seed": 1,
    "shared_prefix": 32,
    "prompt": {"median": 24, "sigma": 0.5, "min": 16, "max": 48},
    "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 40},
    "launcher": ["--slots", "4", "--cache-len", "112", "--chunk", "32",
                 "--page-size", "16", "--num-blocks", "0",
                 "--hold-pages", "0"],
    "check_requests": 8,
}


def bench(cell_name: str) -> dict:
    """A BENCHMARK.json holding one tiny cell, with the repository's own
    metric lists."""
    import json
    from bench.harness.spec import CHECKOUT
    with open(CHECKOUT / "BENCHMARK.json") as f:
        b = json.load(f)
    cell = {"name": cell_name, "config": "tiny", "traffic": "tiny",
            "chips": 1, "why": "CPU test"}
    for e in b["end_to_end"] + b["per_layer"]:
        e.pop("workloads", None)
    b["workloads"] = [cell]
    return b
