"""A checkout with a tiny configuration beside the real ones, for driving
whole runs on the CPU: the harness is the real one, only the sizes are
small and the look for a chip is skipped."""
import json
import os
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

CONFIG = {"name": "tiny", "hidden_size": 128, "intermediate_size": 256,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "vocab_size": 1024,
          "serving": {"medusa_heads": 2, "medusa_top_k": 3, "tree_width": 4,
                      "chunk": 4, "prefill_chunk": 16, "page_size": 8}}
TRAFFIC = {
    "closed": {"loop": "closed", "clients": 1, "batch": 1,
               "prompt": {"dist": "loguniform", "min": 20, "max": 40},
               "output": {"dist": "loguniform", "min": 12, "max": 24},
               "block": 8, "drain_s": 20, "check_requests": 32},
    "open": {"loop": "open", "batch": 4, "rate": 12.0,
             "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.5,
                        "min": 18, "max": 60},
             "output": {"dist": "loguniform", "min": 12, "max": 24},
             "drain_s": 20, "check_requests": 4},
}
# the tiny cells' limits.  Widest gap: sound bf16 runs read up to ~1e-2,
# a token altered where it is produced ~0.5 (the logit scale here).  Mean
# gap over ~400 compared tokens, 12 seeds on the CPU: sound runs read at
# most 8.1e-5, the int8 control at least 2.9e-4.
LIMITS = {"max_logit_gap": 0.05, "mean_logit_gap": 1.6e-4,
          "min_tokens_checked": 40}


def make_root(tmp: Path) -> Path:
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", tmp / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench/configs/qwen2-0.5b.json").read_text())
    cfg.update(CONFIG)
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    for mix, t in TRAFFIC.items():
        (tmp / f"bench/traffic/tiny-{mix}.json").write_text(json.dumps(t))
        (tmp / f"bench/checks/tiny.{mix}.json").write_text(
            json.dumps(LIMITS))
        spec["workloads"].append({"name": f"tiny.{mix}", "config": "tiny",
                                  "traffic": f"tiny-{mix}", "chips": 1,
                                  "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def no_chip_check(chips):
    """Stands in for the look for a chip; peaks of the chip the cells run
    on, so the readers have a table."""
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}
